from repro.kernels.bsr_spmm.ops import bsr_beamform  # noqa: F401
