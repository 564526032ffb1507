"""Sharded MoE dispatch (moe_ffn_sharded) vs the pjit baseline.

The §Perf cell-1 fix: device-local dispatch + one psum. Equality gate runs
on an 8-device subprocess mesh with no-drop capacity so routing matches.
"""
import numpy as np
import pytest

from conftest import run_multidevice

_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from dataclasses import replace
from jax.sharding import AxisType
from repro.configs import get_config, reduced
from repro.models import build_model
from repro.models.common import use_sharding_rules
from repro.launch.sharding import DEFAULT_RULES, make_resolver

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
cfg = reduced(get_config("{arch}"))
cfg = replace(cfg, moe_capacity_factor=float(cfg.n_experts))
api = build_model(cfg)
params = api.init_params(jax.random.PRNGKey(0))
batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)}}
l1, a1 = api.forward(params, batch)  # baseline pjit path (no mesh context)
resolver = make_resolver(mesh, DEFAULT_RULES())
with mesh, use_sharding_rules(resolver, mesh):
    l2, a2 = jax.jit(lambda p, b: api.forward(p, b))(params, batch)
d = float(jnp.max(jnp.abs(l1 - l2)))
assert d < 2e-3, d
# aux differs by estimator (per-shard stats vs global); same ballpark only
assert 0.5 < float(a2) / max(float(a1), 1e-9) < 2.0, (float(a1), float(a2))
print("OK", d)
"""


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "granite-moe-1b-a400m"])
def test_sharded_moe_matches_baseline(arch):
    out = run_multidevice(_CODE.format(arch=arch), n_devices=8, timeout=900)
    assert "OK" in out
