"""Plain float32 reference of the Llama-style dense decoder with linear
RoPE scaling, and its weights.

The yardstick for every configuration whose file says ``"model_type":
"llama_rope_linear"``.  It is ``llama.py`` in every respect but one: the
configuration's ``rope_scaling`` (``{"type": "linear", "factor": f}``,
as Hugging Face's ``LlamaRotaryEmbedding`` applies it) divides each
position by ``f`` before it is rotated:

  angle(pos, i) = (pos / f) * rope_theta ** (-2i / head_dim)

This module loads a private copy of ``llama.py`` and replaces only its
rotary function and the two helpers that carry the factor to it, so
``llama.py`` itself, and every configuration that names it, is
untouched.  Everything else (weights, ``to_program``, ``logits_at``,
loss, gradients, AdamW) is the copy's own, read through this module.
"""
from __future__ import annotations

import importlib.util
import os


def _private_llama():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "llama.py")
    spec = importlib.util.spec_from_file_location(
        "bench_model_llama_rope_linear_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_llama = _private_llama()
_dims, _conf_key, _rope = _llama.dims, _llama._conf_key, _llama._rope

FACTOR = "rope_scaling.factor"   # the factor's key among scalar settings


def factor(conf: dict) -> float:
    """The linear scaling factor of a configuration (1 without any)."""
    if FACTOR in conf:
        return float(conf[FACTOR])
    scaling = conf.get("rope_scaling")
    if not scaling:
        return 1.0
    if scaling.get("type", scaling.get("rope_type")) != "linear":
        raise ValueError(f"not linear RoPE scaling: {scaling!r}")
    return float(scaling["factor"])


def dims(conf: dict) -> dict:
    """``llama.dims``, with ``theta`` the pair (rope_theta, factor) that
    the rotary function below takes."""
    m = _dims(conf)
    m["theta"] = (m["theta"], factor(conf))
    return m


def conf_key(conf: dict):
    """``llama``'s cache key of a configuration, the factor included (it
    sits in a nested group, which that key leaves out)."""
    return _conf_key(conf) + ((FACTOR, factor(conf)),)


def rope(x, pos, theta_factor):
    """x: (T, heads, dh), rotate_half layout; positions divided by the
    factor."""
    theta, f = theta_factor
    return _rope(x, pos.astype("float32") / f, theta)


_llama.dims, _llama._conf_key, _llama._rope = dims, conf_key, rope


def __getattr__(name):
    return getattr(_llama, name)
