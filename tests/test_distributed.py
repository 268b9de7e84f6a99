"""Distribution tests: sharding rules, small-mesh pjit training parity,
pipeline parallelism (all on forced host devices)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.sharding import rules

# NOTE: multi-device behaviours run in subprocesses so this test module can
# keep the default 1-device config (per the dry-run isolation rule).

_SUBPROC_ENV = {**os.environ,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
                "PYTHONPATH": "src"}


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=_SUBPROC_ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_param_spec_rules():
    # spec derivation is mesh-shape arithmetic; use abstract mesh via
    # production mesh on 512 fake devices is heavy — use small subprocess
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.sharding.rules import param_spec
        mesh = make_mesh((2, 4), ("data", "model"))

        class KP:
            def __init__(self, key): self.key = key

        # column-parallel qkv: (embed, heads*dh)
        s = param_spec((KP("blocks"), KP("attn"), KP("wq")), (30, 512, 256),
                       mesh)
        assert s == P(None, ("data",), "model"), s
        # row-parallel wo
        s = param_spec((KP("attn"), KP("wo")), (256, 512), mesh)
        assert s == P("model", ("data",)), s
        # embedding (vocab, embed)
        s = param_spec((KP("embed"), KP("table")), (1024, 512), mesh)
        assert s == P("model", ("data",)), s
        # MoE expert stack (L, E, D, F): expert on model
        s = param_spec((KP("moe"), KP("w_gate")), (4, 8, 64, 128), mesh)
        assert s == P(None, "model", ("data",), None), s
        # indivisible dims fall back to replication
        s = param_spec((KP("attn"), KP("wq")), (30, 7, 9), mesh)
        assert s == P(None, None, None), s
        # scalars
        s = param_spec((KP("opt"), KP("step")), (), mesh)
        assert s == P(), s
        print("rules-ok")
    """)
    assert "rules-ok" in out


def test_small_mesh_train_matches_single_device():
    """pjit on a 2x2 mesh must reproduce single-device training losses."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.configs.shapes import ShapeCfg
        from repro.launch.mesh import make_mesh
        from repro.launch.train import run

        cfg = configs.get("smollm-135m").reduced()
        shape = ShapeCfg("t", "train", 32, 4)
        mesh1 = make_mesh((1, 1), ("data", "model"))
        _, l1 = run(cfg, shape, mesh=mesh1, steps=4, log_every=100)
        mesh4 = make_mesh((2, 2), ("data", "model"))
        _, l4 = run(cfg, shape, mesh=mesh4, steps=4, log_every=100)
        np.testing.assert_allclose(l1, l4, rtol=2e-3, atol=2e-3)
        print("parity-ok", l1, l4)
    """)
    assert "parity-ok" in out


_SHARDED_OPS = {
    # each: build args, then `f(*args)` (and its gradients where the op
    # has a VJP) is compared unsharded vs 2x2
    "matmul": """
        args = (rnd(64, 128), rnd(128, 256), rnd(256))
        f = lambda x, w, b: matmul(x, w, b, activation="gelu")
        grads = True
    """,
    "brgemm": """
        args = (rnd(3, 64, 32), rnd(3, 32, 128), rnd(64, 128))
        f = lambda a, b, c0: brgemm(a, b, c0=c0, beta=0.5)
        grads = True
    """,
    "batched_matmul": """
        args = (rnd(3, 64, 32), rnd(32, 128))
        f = batched_matmul
        grads = False
    """,
    "matmul_int8": """
        args = (rnd(64, 128), rnd(128, 256))
        f = lambda x, w: matmul(x, w, quant="int8")
        grads = False
    """,
    "flash_attention": """
        args = (rnd(4, 4, 128, 64), rnd(4, 2, 128, 64), rnd(4, 2, 128, 64))
        f = flash_attention
        grads = True
    """,
    "conv2d": """
        args = (rnd(4, 6, 6, 8), rnd(3, 3, 8, 16), rnd(16))
        f = lambda x, w, b: conv2d(x, w, b, padding=1, activation="relu")
        grads = True
    """,
}


@pytest.mark.parametrize("op", sorted(_SHARDED_OPS))
def test_sharded_pallas_op_matches_unsharded(op):
    """Under a 2x2 mesh each Pallas op runs per shard in jax.shard_map
    (interpret mode here); values and gradients match the unsharded op."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        import repro
        from repro.kernels.brgemm import matmul, brgemm, batched_matmul
        from repro.kernels.conv2d import conv2d
        from repro.kernels.flash_attention import flash_attention
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        rnd = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    """ + _SHARDED_OPS[op] + """
        loss = lambda *a: (f(*a) ** 2).sum()
        g = jax.value_and_grad(loss, argnums=tuple(range(len(args)))) \\
            if grads else f
        # a fresh function per context: jit captures the dispatch context
        # when it traces, and reuses that trace for the same function
        make = lambda: jax.jit(lambda *a: g(*a))
        with repro.use(backend="pallas"):
            want = make()(*args)
        mesh = make_mesh((2, 2), ("data", "model"))
        with repro.use(backend="pallas", mesh=mesh):
            run = make()
            assert "manual_computation" in run.lower(*args).as_text()
            got = run(*args)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-3)
        print("sharded-ok")
    """)
    assert "sharded-ok" in out


def test_pipeline_parallel_parity():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline import pipeline_apply
        mesh = make_mesh((4,), ("stage",))
        S, M, mb, d = 4, 8, 2, 16
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(S, d, d)) * (1/d)**0.5, jnp.float32)
        x = jnp.asarray(rng.normal(size=(M, mb, d)), jnp.float32)
        layer = lambda p, h: jax.nn.relu(h @ p["w"])
        y = pipeline_apply({"w": w}, x, layer, mesh=mesh, n_microbatches=M)
        ref = x
        for s in range(S):
            ref = jax.nn.relu(ref @ w[s])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("pp-ok")
    """)
    assert "pp-ok" in out


def test_make_production_mesh_shapes():
    out = _run("""
        import os
        # this subprocess uses 8 devices; production mesh needs 512 — only
        # check the axis bookkeeping helpers here
        from repro.launch.mesh import make_mesh, dp_axes, dp_size, model_size
        m = make_mesh((2, 4), ("data", "model"))
        assert dp_axes(m) == ("data",)
        assert dp_size(m) == 2 and model_size(m) == 4
        m2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert dp_axes(m2) == ("pod", "data")
        assert dp_size(m2) == 4
        print("mesh-ok")
    """)
    assert "mesh-ok" in out


def test_dryrun_artifacts_complete():
    """Every (arch x shape x mesh) cell has a recorded outcome, and every
    recorded outcome is ok or an explained skip."""
    import json
    import pathlib
    art = pathlib.Path(__file__).parent.parent / "benchmarks" / "artifacts"
    if not art.exists():
        pytest.skip("dry-run artifacts not generated yet")
    from repro.configs.shapes import SHAPES
    missing, bad = [], []
    for arch in configs.ARCH_NAMES:
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                f = art / f"dryrun_{arch}_{shape}_{mesh}.json"
                if not f.exists():
                    missing.append(f.name)
                    continue
                rec = json.loads(f.read_text())
                if rec["status"] == "error":
                    bad.append(f.name)
                elif rec["status"] == "skipped" and not rec.get("reason"):
                    bad.append(f.name)
    assert not missing, f"missing cells: {missing}"
    assert not bad, f"failed cells: {bad}"
