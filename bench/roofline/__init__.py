"""Operations and bytes of the program's kernels, computed from the shapes
of each call as the device trace names them. One module per kernel family;
``least_seconds`` is the time the call could take at the device's peaks."""
