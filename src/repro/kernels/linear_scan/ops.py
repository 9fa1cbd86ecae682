"""jit'd wrapper for the RWKV6/GLA chunked recurrence kernel."""
from __future__ import annotations

from repro.kernels.linear_scan.linear_scan import rwkv6_scan
from repro.kernels.linear_scan.ref import rwkv6_ref


def linear_scan(r, k, v, logw, u, use_kernel: bool = True, interpret: bool | None = None):
    if use_kernel:
        return rwkv6_scan(r, k, v, logw, u, interpret=interpret)
    return rwkv6_ref(r, k, v, logw, u)
