"""The comparison that decides ``correct`` passes the program, fails its
lower-precision control, and fails a run with the timed path broken.

These drive the whole run of a cell but for the look for a chip, at a
size the CPU holds; the limits are this size's own
(``data/tiny-limits.json``)."""
import pytest

from harness import serve, train


@pytest.mark.parametrize("seed", [2**33 + 5, 12])
def test_serving_program_passes_and_int8_control_fails(tiny, seed):
    res = serve.run(tiny("serve", seed=seed, control=True))
    assert res["correct"], res["checks"]
    ctl = res["control"]["int8"]
    assert not ctl["correct"] and ctl["checks"], ctl


def test_training_program_passes_and_controls_and_faults_fail(tiny):
    res = train.run(tiny("train", control=True))
    assert res["correct"], res["checks"]
    for what in ("fp8", "half_batch", "stale_weights"):
        assert not res["control"][what]["correct"], (what, res["control"])


def _altered_tokens(monkeypatch, vocab):
    from repro.serve.engine import ContinuousEngine

    emit = ContinuousEngine._emit

    def altered(self, rid, tok, finished):
        return emit(self, rid, (tok + 1) % vocab, finished)

    monkeypatch.setattr(ContinuousEngine, "_emit", altered)


def _frozen_decode_cache(monkeypatch, vocab):
    from repro.models import api

    decode = api.decode_step_paged

    def frozen(params, tokens, cfg, data, *a, **kw):
        logits, _, scales = decode(params, tokens, cfg, data, *a, **kw)
        return logits, data, scales

    monkeypatch.setattr(api, "decode_step_paged", frozen)


@pytest.mark.parametrize("fault", [_altered_tokens, _frozen_decode_cache],
                         ids=["token_altered", "state_unchanged"])
def test_serving_fault_reads_incorrect(tiny, monkeypatch, fault):
    fault(monkeypatch, 256)
    res = serve.run(tiny("serve"))
    assert not res["correct"], res["checks"]


def _frozen_state(step):
    def run(state, batch):
        return state, step(state, batch)[1]
    return run


def _half_batch(step):
    def run(state, batch):
        return step(state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    return run


@pytest.mark.parametrize("fault", [_frozen_state, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_training_fault_reads_incorrect(tiny, monkeypatch, fault):
    from repro.train import train_step as ts

    make = ts.make_train_step
    monkeypatch.setattr(ts, "make_train_step",
                        lambda *a, **kw: fault(make(*a, **kw)))
    res = train.run(tiny("train"))
    assert not res["correct"], res["checks"]
