"""Pallas TPU kernels for the perf-critical compute layers.

<name>.py  : pl.pallas_call + explicit BlockSpec VMEM tiling
cut_ad.py  : {mv, vm, outer} primitive closure (kernel-backed autodiff
             to arbitrary order for the cut contraction)
ops.py     : jit'd public wrappers (Mosaic on TPU, interpreter elsewhere)
platform.py: the one place a kernel's `interpret` flag resolves
ref.py     : pure-jnp oracles (the correctness source of truth)
"""
from repro.kernels import cut_ad, ops, ref
from repro.kernels.ops import (cut_eval, flash_attention, fused_cut_round,
                               mlstm_chunk, mlstm_sequence)
