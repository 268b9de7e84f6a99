import os
import sys
import time

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


@pytest.fixture
def tiny():
    """A tiny cell's Context: the Llama block at CPU size, no chip."""
    from harness import common

    def make(traffic: str, *, seed: int = 2**33 + 5, seconds: float = 2.0,
             control: bool = False, **override):
        data = os.path.join(HERE, "data")
        t = common.load_json(os.path.join(data, f"tiny-{traffic}.json"))
        t.update(override)
        return common.Context(
            workload=f"tiny.{traffic}",
            conf=common.load_json(os.path.join(data, "tiny-llama.json")),
            traffic=t,
            cell=common.load_json(os.path.join(data, "tiny-limits.json")),
            seed=seed, seconds=seconds, trace=False, chips=1,
            t_process=time.perf_counter(), require_chip=False,
            control=control)
    return make
