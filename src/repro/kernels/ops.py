"""Jit'd public entries for the Pallas kernels.

Each call picks the kernel's mode from the platform it runs on: on a TPU
the kernel compiles to a Mosaic custom call; on any other backend (the
CPU tests) its body runs in the Pallas interpreter.  The raw entries in
the kernel modules have no default for ``interpret``: every caller
states the mode.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import quant_matmul as _qm
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssm_scan as _ss


def _entry(kernel, *static_argnames):
    jitted = jax.jit(kernel,
                     static_argnames=static_argnames + ("interpret",))

    @functools.wraps(kernel)
    def call(*args, **kwargs):
        return jitted(*args, interpret=jax.default_backend() != "tpu",
                      **kwargs)

    return call


flash_attention = _entry(_fa.flash_attention, "causal", "softcap",
                         "block_q", "block_k")
decode_attention = _entry(_da.decode_attention, "block_s", "max_len")
paged_decode_attention = _entry(_da.paged_decode_attention, "max_len")
int8_matmul = _entry(_qm.int8_matmul, "block_m", "block_n")
ssm_scan = _entry(_ss.ssm_scan, "chunk", "block_d")
rmsnorm = _entry(_rn.rmsnorm, "eps", "block_rows")
