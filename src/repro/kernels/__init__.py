"""Pallas TPU kernels for the framework's compute hot spots.

Each kernel subpackage has:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, reshape, interpret switch)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels:
  flash_attention  — causal GQA attention, online softmax (train/prefill hot spot)
  masked_aggregate — ACSP-FL Eq. (1): fused masked weighted client average
                     (the server hot spot of the paper)
  ssm_scan         — Mamba-1 selective scan, chunked (falcon-mamba / jamba)
  quantize         — per-block absmax int8/int4 (de)quantization with
                     stochastic rounding (repro.comm wire-format hot path)

The default ``interpret`` follows the backend: on a TPU the compiled
kernels run (interpret=False); on the CPU, where the tests run, they run in
interpret mode or, for the quantize pair, dispatch to the jnp reference.
"""

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.masked_aggregate.ops import masked_aggregate
from repro.kernels.quantize.ops import dequantize, quantize
from repro.kernels.ssm_scan.ops import ssm_scan

__all__ = ["flash_attention", "masked_aggregate", "ssm_scan", "quantize", "dequantize"]
