"""Pallas kernels for FREYJA's compute (see ops.py for the public entry
points, which decide between native TPU compilation and interpret mode, and
ref.py for the oracles):

  minhash                        — signature build (ingest; compiles on TPU)
  lsh_probe                      — LSH bucket probe (serving; compiles on TPU)
  profile_distance / fused_score — pairwise profile distances (+ oblivious
                                    GBDT scoring fused in-VMEM)
  gbdt_infer                     — standalone oblivious-GBDT ensemble
  quality_cdf                    — truncated-Gaussian quality metric

The last three families serve only tests and the paper-table benchmarks;
the TPU compiler refuses them today (ops.py quotes its errors).
"""
