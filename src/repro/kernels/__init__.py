"""Pallas TPU kernels (+ jnp oracles) for the perf-critical compute layers:
flash attention, RG-LRU scan, RWKV6 WKV.  See ops.py for public wrappers."""
from . import ops, ref
from .flash_attention import flash_attention
from .rglru_scan import rglru_scan_kernel
from .wkv6 import wkv6_bwd, wkv6_fwd
