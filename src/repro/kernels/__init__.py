"""Pallas TPU kernels for the STKDE compute hot-spot.

stkde_tile.py — PB-SYM tile accumulation as an MXU GEMM (pallas_call +
                explicit BlockSpec VMEM tiling)
ops.py        — jit'd public wrappers (bucketing + kernel + slice)
ref.py        — pure-jnp oracles for allclose testing
"""
from .ops import stkde_tiled, default_tile, tiled_inputs
from .stkde_tile import stkde_tiles_pallas
from .ref import stkde_tiles_ref

__all__ = [
    "stkde_tiled",
    "default_tile",
    "tiled_inputs",
    "stkde_tiles_pallas",
    "stkde_tiles_ref",
]
