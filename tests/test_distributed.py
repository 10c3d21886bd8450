"""Distributed decompositions (paper §2.4) — runs in a subprocess with 8
fake devices so the main test process keeps the default single device."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core.formats import BlockELL
    from repro.core.distributed import (spmm_1p5d, spmm_2d, spmm_2p5d,
                                        allgather_matmul_overlap)

    rng = np.random.default_rng(2)
    M, N, D = 256, 256, 64
    dense = (rng.normal(size=(M, N)) * (rng.random((M, N)) < 0.2)) \\
        .astype(np.float32)
    h = rng.normal(size=(N, D)).astype(np.float32)
    expected = dense @ h
    ell = BlockELL.from_dense(dense, bm=32, bn=32)

    from repro.sharding.specs import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    for name, fn in [("1.5D", spmm_1p5d), ("2D", spmm_2d)]:
        y = fn(ell, jnp.asarray(h), mesh)
        np.testing.assert_allclose(np.asarray(y), expected,
                                   rtol=2e-4, atol=2e-4)
        print(name, "OK")

    mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    y = spmm_2p5d(ell, jnp.asarray(h), mesh3)
    np.testing.assert_allclose(np.asarray(y), expected, rtol=2e-4, atol=2e-4)
    print("2.5D OK")

    x = rng.normal(size=(16, 64)).astype(np.float32)
    w = rng.normal(size=(64, 32)).astype(np.float32)
    ym = allgather_matmul_overlap(jnp.asarray(x), jnp.asarray(w), mesh,
                                  axis="model")
    np.testing.assert_allclose(np.asarray(ym), x @ w, rtol=2e-4, atol=2e-4)
    print("collective-matmul OK")

    # sharded train step parity vs single-device (tiny model)
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models.transformer import init_lm
    from repro.train.loop import TrainConfig, init_train_state, \\
        make_train_step
    from repro.train.optimizer import OptConfig
    from repro.data.pipeline import make_lm_batch, DataConfig
    from repro.sharding.specs import param_sharding_tree, data_sharding_tree
    from repro.sharding import ctx as shard_ctx

    cfg = dataclasses.replace(get_smoke_config("granite-20b"),
                              dtype="float32")
    tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=0,
                                     total_steps=10))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = init_train_state(params, tcfg)
    batch = make_lm_batch(cfg, 32, 8, 0, DataConfig(seed=0))
    step = make_train_step(cfg, tcfg)
    p1, _, m1 = jax.jit(step)(params, state, batch)

    # fresh step fn for the sharded run: jit reuses the traced jaxpr per
    # function object, and step's first trace (no mesh installed) has no
    # shard_hint constraints baked in
    step2 = make_train_step(cfg, tcfg)
    p_sh = param_sharding_tree(params, mesh)
    s_sh = param_sharding_tree(state, mesh)
    b_sh = data_sharding_tree(batch, mesh, 8)
    shard_ctx.set_mesh(mesh)
    p2, _, m2 = jax.jit(step2, in_shardings=(p_sh, s_sh, b_sh),
                        out_shardings=(p_sh, s_sh, None))(
        params, state, batch)
    shard_ctx.clear_mesh()
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-3
    import jax.tree_util as jtu
    diff = jtu.tree_map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    # first adam step quantizes updates to ~+-lr; reduction-order noise on
    # near-zero grads can flip signs, so allow a few lr quanta of drift
    assert max(jtu.tree_leaves(diff)) < 3e-3, max(jtu.tree_leaves(diff))
    print("sharded-train-parity OK")
""")


@pytest.mark.slow
def test_distributed_spmm_and_sharded_train():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    for tag in ("1.5D OK", "2D OK", "2.5D OK", "collective-matmul OK",
                "sharded-train-parity OK"):
        assert tag in out.stdout


def _one_device_ell_problem():
    import numpy as np

    from repro.core.formats import BlockELL

    rng = np.random.default_rng(0)
    dense = (rng.normal(size=(64, 64)) * (rng.random((64, 64)) < 0.2)) \
        .astype(np.float32)
    h = rng.normal(size=(64, 16)).astype(np.float32)
    return dense, BlockELL.from_dense(dense, bm=16, bn=16), h


@pytest.mark.parametrize("backend,expect_kernel", [("tpu", True),
                                                   ("cpu", False)])
def test_spmm_1p5d_plans_the_kernel_from_the_backend(monkeypatch, backend,
                                                     expect_kernel):
    """Without a use_kernel argument the 1.5D path follows the
    dispatcher's backend rule: the Pallas kernel on TPU, the reference
    elsewhere (never a hard-coded reference default)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import distributed
    from repro.sharding.specs import make_mesh

    seen = []
    real = distributed.spmm_blockell

    def recording(ell, h, *, use_kernel):
        seen.append(use_kernel)
        return real(ell, h, use_kernel=False)  # the CPU runs the reference

    monkeypatch.setattr(distributed, "spmm_blockell", recording)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    dense, ell, h = _one_device_ell_problem()
    mesh = make_mesh((1,), ("data",))
    y = distributed.spmm_1p5d(ell, jnp.asarray(h), mesh)
    assert seen == [expect_kernel]
    np.testing.assert_allclose(np.asarray(y), dense @ h, rtol=1e-5,
                               atol=1e-5)
