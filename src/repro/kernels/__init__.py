"""Pallas TPU kernels for the compute hot spots the paper optimizes.

fused_iter — the WHOLE PIPECG iteration: banded DIA SPMV + 8 VMAs +
             Jacobi PC + dot partials in one grid walk, so one iteration
             launches one kernel (Rupp et al., arXiv 1410.4054).
fused_vma  — PIPECG iteration core: 8 VMAs + Jacobi PC + dot partials,
             one HBM pass (paper §V-B kernel fusion, extended).
fused_dot  — gamma/delta/(u,u) in one pass (merged reductions).
spmv_dia   — banded/stencil SPMV (TPU-native replacement for CSR SPMV).
spmv_bell  — Block-ELLPACK SPMV for general sparsity (interpret mode only:
             its gather does not compile for TPU, so no solver path uses it).
fused_adam — the fusion idea applied to the LM training substrate.
flash_attn — single-pass causal attention (online softmax in VMEM scratch);
             the fix for the memory-dominant roofline cells (§Perf).

Every kernel ships kernel.py (pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (pure-jnp oracle); tests sweep shapes/dtypes with
interpret=True on CPU.
"""
from .flash_attn import flash_attention, flash_attention_ref
from .fused_adam import fused_adamw, fused_adamw_ref
from .fused_dot import fused_dots, fused_dots_ref
from .fused_iter import fused_iter_ref, fused_iter_step, fused_iter_tile
from .fused_vma import fused_vma_dots, fused_vma_dots_ref
from .spmv_bell import spmv_bell_pallas, spmv_bell_ref
from .spmv_dia import spmv_dia_pallas, spmv_dia_ref

__all__ = [
    "flash_attention",
    "flash_attention_ref",
    "fused_adamw",
    "fused_adamw_ref",
    "fused_dots",
    "fused_dots_ref",
    "fused_iter_ref",
    "fused_iter_step",
    "fused_iter_tile",
    "fused_vma_dots",
    "fused_vma_dots_ref",
    "spmv_bell_pallas",
    "spmv_bell_ref",
    "spmv_dia_pallas",
    "spmv_dia_ref",
]
