"""MatPIM core: cycle-accurate crossbar reproduction of the paper.

Public API:
    Crossbar               — stateful-logic interpreter (validates + counts)
    compile_program        — lower a Program to a packed executable trace
    execute                — vectorized batched executors (numpy / jax)
    CrossbarPlan           — shared compile-then-execute plan base class
    MatvecPlan             — §II-A balanced full-precision matrix-vector
    BinaryMatvecPlan       — §II-B partition-tree binary matrix-vector
    ConvPlan               — §III-A/B input-parallel balanced convolution
    BinaryConvPlan         — §III-C binary convolution
    tiling                 — multi-crossbar scale-out (tiled matvec / conv)
    latency                — Table I/II regeneration + published numbers
    pallas_exec            — "pallas" backend: traces on repro.kernels
"""
from .binary_conv import BinaryConvPlan, matpim_binary_conv2d
from .binary_matvec import (BinaryMatvecPlan, NaiveBinaryMatvecPlan,
                            matpim_binary_matvec)
from .compile import (CompiledProgram, FusedSchedule, Segment,
                      compile_program, fuse_program)
from .conv import ConvPlan, matpim_conv2d
from .crossbar import Crossbar, SchedulingError, decode_uint, encode_uint
from .engine import (EngineResult, available_backends, execute, have_jax,
                     parse_backend)
from .matvec import MatvecPlan, matpim_matvec
from .plan import CrossbarPlan
from .tiling import (TiledBinaryMatvec, TiledConv2d, TiledMatvec, TiledResult,
                     tiled_binary_conv2d, tiled_binary_matvec, tiled_conv2d,
                     tiled_matvec)

__all__ = [
    "BinaryConvPlan", "BinaryMatvecPlan", "CompiledProgram", "ConvPlan",
    "Crossbar", "CrossbarPlan", "EngineResult", "FusedSchedule",
    "MatvecPlan", "NaiveBinaryMatvecPlan", "SchedulingError", "Segment",
    "TiledBinaryMatvec", "TiledConv2d", "TiledMatvec", "TiledResult",
    "available_backends", "compile_program", "decode_uint", "encode_uint",
    "execute", "fuse_program", "have_jax", "matpim_binary_conv2d",
    "matpim_binary_matvec", "matpim_conv2d", "matpim_matvec",
    "parse_backend", "tiled_binary_conv2d", "tiled_binary_matvec",
    "tiled_conv2d", "tiled_matvec",
]
