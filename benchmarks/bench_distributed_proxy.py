"""Paper Figure 10 proxy: distributed scaling of GNMT-style LSTM / CNN
training.

With one physical core, wall-time scaling is meaningless; the honest
CPU-measurable quantity is the *communication footprint* of the SPMD
program as the mesh grows — the thing that determines the paper's strong
scaling.  For data-parallel meshes of 2/4/8 devices this lowers the smollm
train step and reports all-reduce bytes per device per step (the gradient
volume), which is the Fig-10 x-axis driver, plus the model-flops per
device (perfect-scaling numerator).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_CODE = """
import json
import jax, jax.numpy as jnp
from repro import configs
from repro.configs.shapes import ShapeCfg
from repro.launch.mesh import make_mesh
from repro.sharding import rules
from repro.sharding.annotate import use_rules
from repro.train import optimizer as opt, train_step as ts
from repro.launch.dryrun import collective_bytes

cfg = configs.get("smollm-135m").reduced()
shape = ShapeCfg("t", "train", 128, 8)
ocfg = opt.AdamWCfg()
ndev = {ndev}
mesh = make_mesh((ndev, 1), ("data", "model"))
with mesh, use_rules(rules.activation_rules(mesh)):
    state = ts.abstract_state(cfg, ocfg)
    import repro.models.api as api
    batch = api.input_specs(cfg, shape)
    st_sh = rules.param_shardings(state, mesh)
    b_sh = rules.batch_shardings(batch, mesh)
    lowered = jax.jit(ts.make_train_step(cfg, ocfg),
                      in_shardings=(st_sh, b_sh)).lower(state, batch)
    compiled = lowered.compile()
    coll = collective_bytes(compiled.as_text())
    cost = compiled.cost_analysis() or {{}}
    print(json.dumps({{"coll": coll.get("total", 0),
                       "ar": coll.get("all-reduce", 0),
                       "flops": cost.get("flops")}}))
"""


def run():
    failed = []
    for ndev in (2, 4, 8):
        # the children lower for fake host devices: pinned to the CPU so
        # they never contend for an accelerator the parent holds
        env = {**os.environ,
               "XLA_FLAGS": f"--xla_force_host_platform_device_count={ndev}",
               "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": "src"}
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_CODE.format(ndev=ndev))],
            capture_output=True, text=True, env=env)
        if r.returncode != 0:
            emit(f"fig10_dp{ndev}", 0.0, f"ERROR:{r.stderr[-120:]}")
            failed.append(ndev)
            continue
        data = json.loads(r.stdout.strip().splitlines()[-1])
        emit(f"fig10_dp{ndev}_collective", 0.0,
             f"{data['coll'] / 1e6:.1f}MB/dev/step")
        emit(f"fig10_dp{ndev}_flops", 0.0,
             f"{(data['flops'] or 0) / 1e9:.1f}GFLOP/dev/step")
    if failed:
        raise RuntimeError(f"lowering failed on {failed} host devices")


if __name__ == "__main__":
    run()
