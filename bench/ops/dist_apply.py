"""Driver of closed-loop distributed H^2 applications (paper §6.1, Fig. 9
HGEMV, the operator in block rows over the configuration's ``p`` chips).

``apply.py``'s traffic on the distributed path.  Set-up compresses the
block-row operator to the configuration's ``tol`` once
(``make_dist_compress``) and draws the pool of ``pool`` blocks X ``[N,
nv]`` on the chips, each split by block rows (``P("blk")``), with
``apply.py``'s entries and probe columns.  Unit i applies the operator to
X[i mod pool] with ``make_dist_matvec`` (its default comm, schedule and
backend) and waits for the application ``ahead`` units before it;
``drain`` waits for the rest (``apply.unit``, ``apply.drain``).

Answers compared after the window, as in ``apply.py`` (``apply.compare``):
the sharded factors are read back to the host
(``reference.dist_answer.to_host``) into the form that
``reference.h2_answer.to_host`` gives for a one-chip operator, and
``apply_err``, ``probe_err`` and ``proj_err`` are computed from them by the
same plain code.  One more number:

  block_gap   blocks missing from or extra in the read-back against the
              plain reference's block sets (``dist_answer.block_gap``):
              the partition holds every block of the operator once.

The control and the faults are ``apply.py``'s, and one more fault,
``no-exchange``: the matvec with all that its collectives bring from
other chips (the halo exchange, the root gather) replaced by zeros.
"""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import json
from pathlib import Path

import numpy as np


def _one_chip_driver():
    # the harness loads a driver by its path before the checkout's root is
    # on sys.path, so ``bench.ops.apply`` cannot be imported here yet
    spec = importlib.util.spec_from_file_location(
        "bench_ops_apply", Path(__file__).with_name("apply.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_apply = _one_chip_driver()
probe_points, probe_columns = _apply.probe_points, _apply.probe_columns
unit, wait, drain = _apply.unit, _apply.wait, _apply.drain
release, control = _apply.release, _apply.control
FAULTS = _apply.FAULTS + ("no-exchange",)


def setup(system: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.seeds import jax_key
    from repro.core.dist import make_dist_matvec

    shape, data = system["compress"](system["data0"])
    if shape not in system["matvecs"]:
        system["matvecs"][shape] = make_dist_matvec(shape, system["mesh"],
                                                    "blk")
    matvec = system["matvecs"][shape]
    pool, nv, probes = traffic["pool"], traffic["nv"], traffic["probes"]
    n = shape.n
    perm = np.asarray(system["perm"])
    inv = np.argsort(perm)
    js = probe_points(cfg, seed, pool * probes).reshape(pool, probes)
    cols = probe_columns(nv, probes)
    rows = NamedSharding(system["mesh"], P("blk"))

    def draw(key, rows_j):
        x = jax.random.normal(key, (pool, n, nv), jnp.float32)
        # the probe columns of each block: e_j in tree order
        x = x.at[:, :, cols].set(0.0)
        b = jnp.arange(pool)[:, None]
        x = x.at[b, rows_j, jnp.asarray(cols)[None, :]].set(1.0)
        return tuple(x[i] for i in range(pool))

    # one block per unit, each on the chips by block rows
    xs = jax.jit(draw, out_shardings=(rows,) * pool)(
        jax_key(jax, seed), jnp.asarray(inv[js]))
    jax.block_until_ready(matvec(data, xs[0]))
    keep = set(int(i) for i in np.random.default_rng([seed, 2]).choice(
        traffic["keep_within"], size=traffic["compare"], replace=False))
    return {"shape": shape, "data": data, "xs": xs, "pool": pool,
            "perm": perm, "probes": js, "keep": keep, "kept": {},
            "matvec": lambda shape, data, x: matvec(data, x),
            "mesh": system["mesh"], "ahead": traffic["ahead"], "sent": collections.deque()}


def summarize(state: dict, outputs: list, elapsed: float) -> dict:
    from bench.reference.dist_answer import to_host, work_shape
    units = len(outputs)
    i, y = state.pop("last")
    state["kept"][i] = y
    state["host_op"] = to_host(state["data"], state["shape"])
    return {"attempted": units, "failed": 0,
            "end_to_end": {"apply_ms": 1e3 * elapsed / units},
            "units": units, "matvecs": units,
            "matvec_shape": work_shape(state["host_op"]),
            "chips": state["shape"].p,
            "nv": state["xs"][0].shape[-1]}


def check(state: dict, outputs: list, cfg: dict, traffic: dict, seed: int,
          limits: dict) -> dict:
    from bench.reference.cheb_h2 import for_config
    from bench.reference.dist_answer import block_gap
    out = _apply.check(state, outputs, cfg, traffic, seed, limits)
    ref = for_config(json.dumps(cfg, sort_keys=True))
    out["block_gap"] = {"value": block_gap(state["host_op"], ref,
                                           state["perm"]),
                        "limit": limits["block_gap"]}
    return out


def _from_own_chip(collective, p: int):
    """``collective`` with what it brings from other chips zeroed: a chip
    keeps its own part of the leading axis (an all-gather's or an
    all-to-all's, in chip order) and nothing of a permutation."""
    import jax
    import jax.numpy as jnp

    def dropped(x, axis_name, *args, **kwargs):
        y = collective(x, axis_name, *args, **kwargs)
        if collective is jax.lax.ppermute:
            return jnp.zeros_like(y)
        own = jnp.arange(y.shape[0]) // (y.shape[0] // p) == \
            jax.lax.axis_index(axis_name)
        return jnp.where(own.reshape((-1,) + (1,) * (y.ndim - 1)), y, 0)
    return dropped


def plant(state: dict, fault: str, frac: float) -> None:
    """``apply.plant``'s faults; ``no-exchange`` traces a matvec of its
    own with every collective answering from the chip's own data alone."""
    if fault != "no-exchange":
        return _apply.plant(state, fault, frac)
    import jax
    from unittest import mock

    from repro.core.dist import make_dist_matvec
    broken = make_dist_matvec(state["shape"], state["mesh"], "blk")
    with contextlib.ExitStack() as patches:
        for name in ("ppermute", "all_gather", "all_to_all"):
            patches.enter_context(mock.patch.object(
                jax.lax, name, _from_own_chip(getattr(jax.lax, name),
                                              state["shape"].p)))
        jax.block_until_ready(broken(state["data"], state["xs"][0]))
    state["matvec"] = lambda shape, data, x: broken(data, x)
