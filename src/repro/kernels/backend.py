"""Which implementation a kernel call runs — one policy for every
kernel in this package:

  * "pallas"    — compiled Pallas (TPU target).
  * "interpret" — Pallas interpret mode (kernel body executed in Python;
                  the CPU validation path used by tests).
  * "ref"       — the pure-jnp oracle (default on CPU: fastest correct
                  path where no Mosaic backend exists).
  * "auto"      — pallas on TPU, ref elsewhere.
"""

from __future__ import annotations

import jax


def resolve(backend: str = "auto") -> str:
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "ref"
