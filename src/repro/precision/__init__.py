"""Precision substrate: format descriptors, round-to-format emulation,
and the backend dispatch layer (DESIGN.md §6)."""
from . import dw
from .backend import (DoubleWord, JnpBackend, PallasBackend, PrecisionBackend,
                      available_backends, default_backend, register_backend,
                      resolve_backend, set_default_backend)
from .chop import (chop, chop_matmul, chop_static, chop_stochastic,
                   chop_tree, fma_barrier, rounding_unit, simulate_dtype,
                   tree_sum)
from .formats import (BF16, E4M3, E5M2, FORMAT_ID, FORMAT_LIST, FORMATS, FP16,
                      FP32, FP64, SOLVER_LADDER, SOLVER_LADDER_FP8, TF32,
                      TPU_LADDER, FloatFormat, format_id, get_format,
                      runtime_tables)

__all__ = [
    "chop", "chop_matmul", "chop_static", "chop_stochastic", "chop_tree",
    "fma_barrier", "tree_sum", "rounding_unit",
    "simulate_dtype", "FloatFormat", "get_format", "format_id",
    "FORMATS", "FORMAT_LIST", "FORMAT_ID", "SOLVER_LADDER",
    "SOLVER_LADDER_FP8", "TPU_LADDER",
    "BF16", "FP16", "TF32", "FP32", "FP64", "E4M3", "E5M2", "runtime_tables",
    "PrecisionBackend", "JnpBackend", "DoubleWord", "dw", "PallasBackend", "resolve_backend",
    "default_backend", "set_default_backend", "register_backend",
    "available_backends",
]
