"""The configuration's data tables, taken from the system's scenario catalog
and held to the fingerprint recorded beside the configuration.

The reference reads these tables as the deployment's data (station tree,
prices, arrival rates, car mix, PV), the way a model's reference reads its
seeded weights.  ``check`` compares them with
``bench/configs/<config>.tables.json``, written once when the configuration
was added, so a program change that alters the deployment's data is a
failed check, not a silent change of the reference.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# sampled positions per leaf, spread over the flattened table
_N_SAMPLES = 16
RTOL = 1e-6


def build(config: dict):
    """(env, stacked EnvParams on the host CPU) for the configuration."""
    import jax

    from repro import scenarios
    from repro.core import ChargaxEnv, EnvConfig

    env = ChargaxEnv(EnvConfig(**config["env"]))
    with jax.default_device(jax.devices("cpu")[0]):
        per = [scenarios.make(n).make_params(env) for n in config["scenarios"]]
        params = scenarios.stack_params(per)
    return env, params


def as_dict(params) -> dict:
    """Flat ``{name: numpy array}`` view of a stacked EnvParams; the reward
    weights become ``w_<term>``."""
    out = {}
    for f in dataclasses.fields(params):
        v = getattr(params, f.name)
        if v is None:
            continue
        if f.name == "weights":
            for wf in dataclasses.fields(v):
                out[f"w_{wf.name}"] = np.asarray(getattr(v, wf.name), np.float32)
            continue
        out[f.name] = np.asarray(v)
    return out


def fingerprint(tables: dict) -> dict:
    fp = {}
    for k in sorted(tables):
        a = np.asarray(tables[k])
        flat = a.reshape(-1).astype(np.float64)
        idx = np.linspace(0, flat.size - 1, min(_N_SAMPLES, flat.size)).astype(np.int64)
        fp[k] = {
            "shape": list(a.shape),
            "dtype": str(a.dtype),
            "sum": float(flat.sum()),
            "abs_sum": float(np.abs(flat).sum()),
            "samples": [float(x) for x in flat[idx]],
        }
    return fp


def check(tables: dict, recorded: dict) -> list[str]:
    """Names of the tables that differ from the recorded fingerprint."""
    got = fingerprint(tables)
    bad = sorted(set(got) ^ set(recorded))
    for k in sorted(set(got) & set(recorded)):
        g, r = got[k], recorded[k]
        if g["shape"] != r["shape"] or g["dtype"] != r["dtype"]:
            bad.append(k)
            continue
        scale = max(r["abs_sum"], 1e-30)
        nums = np.array([g["sum"], g["abs_sum"]] + g["samples"])
        want = np.array([r["sum"], r["abs_sum"]] + r["samples"])
        if not np.allclose(nums, want, rtol=RTOL, atol=RTOL * scale / max(np.prod(r["shape"]), 1)):
            bad.append(k)
    return bad
