"""CI-scale dry-run: reduced configs on an 8-device test mesh (subprocess so
the main process keeps 1 device). Exercises the same build_cell path as the
production dry-run: lower + compile + memory/cost analysis + roofline."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import smoke_config, SHAPES
from repro.launch.hlo_cost import loop_aware_cost
from repro.launch.mesh import make_test_mesh
from repro.models.build import build
from repro.optim import adamw_init
from repro.sharding import batch_specs, cache_specs, param_rules
from repro.sharding.ctx import activation_sharding
from repro.train.loop import TrainState, make_train_step

ARCHS = ["llama3.2-3b", "glm4-9b", "mixtral-8x22b", "zamba2-2.7b", "xlstm-350m",
         "whisper-medium", "deepseek-v3-671b", "internvl2-76b"]

mesh = make_test_mesh()  # (4, 2) data x model
ok = []
for arch in ARCHS:
    cfg = smoke_config(arch)
    model = build(cfg)
    rules = param_rules(cfg, multi_pod=False, model_size=2)
    pspecs = model.specs(rules)
    params_sds = model.abstract(jnp.float32)
    opt_sds = jax.eval_shape(adamw_init, params_sds)
    state_sds = TrainState(params_sds, opt_sds, None)
    state_specs = TrainState(pspecs, {"mu": pspecs, "nu": pspecs, "step": P()}, None)
    b, s = 8, 32
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    bspec = {"tokens": P(("data",), None)}
    if cfg.family == "audio":
        batch["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model), jnp.float32)
        bspec["frames"] = P(("data",), None, None)
    if cfg.family == "vlm":
        batch["patches"] = jax.ShapeDtypeStruct((b, cfg.n_patches, cfg.d_model), jnp.float32)
        bspec["patches"] = P(("data",), None, None)
    step = make_train_step(model.loss_fn)
    named = lambda t: jax.tree.map(lambda sp: NamedSharding(mesh, sp), t,
                                   is_leaf=lambda x: isinstance(x, P))
    with jax.set_mesh(mesh), activation_sharding(
        dp=("data",), dp_sizes=(4,), tp="model", tp_size=2
    ):
        compiled = jax.jit(
            step, in_shardings=(named(state_specs), named(bspec))
        ).lower(state_sds, batch).compile()
    mem = compiled.memory_analysis()
    lac = loop_aware_cost(compiled.as_text())
    assert lac["flops"] > 0, arch
    assert mem.argument_size_in_bytes > 0, arch
    ok.append(arch)
print("DRYRUN_SMALL_OK", len(ok))
"""


@pytest.mark.slow
def test_dryrun_reduced_configs_compile_on_test_mesh():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), "..", ".."),
        env=env, timeout=1800,
    )
    assert out.returncode == 0, out.stderr[-5000:]
    assert "DRYRUN_SMALL_OK 8" in out.stdout
