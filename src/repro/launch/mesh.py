"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax initialization).

Mesh geometry (TPU v5e):
* single pod:  (16, 16) = 256 chips, axes ('data', 'model')
* multi-pod:   (2, 16, 16) = 512 chips, axes ('pod', 'data', 'model')

Mapping of the paper's HPC topology (§V): a 'super learner' (one server's
GPUs under NCCL allreduce) becomes one model-parallel group; the learner
ring of AD-PSGD runs over the 'data' axis on one pod and over the 'pod'
axis in the H-ring multi-pod configuration.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.sharding import MeshRules, default_rules, multipod_rules


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, devices=None):
    """Mesh over the local devices (all of them, or ``devices``): ``data``
    of them on the 'data' axis (learners / batch shards), the rest on
    'model'."""
    devices = list(devices or jax.devices())
    n = len(devices)
    data = min(data, n)
    model = max(n // data, 1)
    return _make_mesh((data, model), ("data", "model"),
                      devices=devices[:data * model])


def rules_for(cfg, mesh, *, multi_pod: bool = False) -> MeshRules:
    """MeshRules for one architecture on one mesh (FSDP / expert axis per
    the arch's distribution defaults)."""
    mk = multipod_rules if multi_pod else default_rules
    rules = mk(fsdp=cfg.fsdp, expert_axis=cfg.expert_axis)
    if getattr(cfg, "attn_sharding", "replicated") == "seq":
        # sequence-parallel attention (§Perf): projections sharded on the
        # contracting head_dim (always 16-divisible across the zoo); the
        # attention compute itself is resharded per q-chunk in attn_seq.
        rules["head_dim"] = ("model",)
    return MeshRules(mesh, rules)
