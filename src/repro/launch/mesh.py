"""Mesh construction.

Functions only (never a module-level constant), so importing this module
does not touch jax device state.

Axis roles:
  data  — batch sharding
  model — tensor parallel (heads/ffn/vocab), expert parallel (MoE),
          KV-sequence parallel (flash-decoding), index-row parallel (MIPS)
"""
from __future__ import annotations

import jax
import numpy as np


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small multi-device checks)."""
    return jax.make_mesh(tuple(shape), tuple(axes))


def make_local_mesh(model: int = 1, data: int = 1):
    """Mesh over whatever devices exist locally (CPU tests)."""
    n = len(jax.devices())
    assert model * data <= n, (model, data, n)
    devs = np.asarray(jax.devices()[: model * data]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))
