"""The check that decides ``correct`` fails a broken timed path.

Each test drives a whole run of a cell, cut to a CPU test's size
(``tiny.py``), without the harness's look for a chip, with one fault
planted underneath the timed path, and sees ``correct`` come out false
under the cell's own limits.  The control, the reference computed in
bfloat16 and put in the program's place, fails them too.  A run with no
fault is correct.
"""
from __future__ import annotations

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.netsim as netsim
from lib import harness
from lib.chip import CompileClock
from lib.kinds import Online, Sweep
from tiny import cell, now

CLOCK = CompileClock()
SWEEPS = ["table1.sweep18.kernel", "multipod512.sweep8.kernel",
          "multipod512.sweep8.xla.4chip"]


def run(c, seed=2**31 + 11, seconds=1.0, kind_cls=None):
    out = io.StringIO()
    res = harness.run(c, seed, seconds, False, jax.devices()[:c.chips], now(),
                      CLOCK, out=out, err=io.StringIO(),
                      kind_cls=kind_cls)
    return res


def _sweep_fault(monkeypatch, fault):
    real = netsim.simulate_grid

    def broken(*a, **kw):
        res = real(*a, **kw)
        return jax.tree.map(np.asarray, fault(jax.device_get(res)))

    monkeypatch.setattr(netsim, "simulate_grid", broken)


def state_unchanged(res):
    """Every sample after the first period repeats the first one."""
    return res._replace(**{f: np.repeat(getattr(res, f)[:, :, :1],
                                        getattr(res, f).shape[2], axis=2)
                           for f in res._fields if f.startswith("ts_")})


def half_batch(res):
    """The second half of the lanes is a copy of the first half."""
    K = res.ts_qmax.shape[0]
    return jax.tree.map(
        lambda x: np.concatenate([x[:K // 2], x[:K // 2]])[:K], res)


def one_chip_only(res):
    """Only the first of four devices' lane shards comes back; the other
    devices' lanes hold its lanes in their place."""
    K = res.ts_qmax.shape[0]
    per = -(-K // 4)
    return jax.tree.map(lambda x: np.concatenate([x[:per]] * 4)[:K], res)


def altered(res):
    """One sampled throughput of one lane is 1% off where produced."""
    t = res.ts_throughput.copy()
    t[1, 0, t.shape[2] // 2, 0] *= 1.01
    return res._replace(ts_throughput=t)


@pytest.mark.parametrize("name", SWEEPS)
def test_sweep_sound_run_is_correct(name):
    assert run(cell(name))["correct"]


@pytest.mark.parametrize("name", SWEEPS)
@pytest.mark.parametrize("fault", [state_unchanged, half_batch, altered],
                         ids=lambda f: f.__name__)
def test_sweep_fault_fails(monkeypatch, name, fault):
    _sweep_fault(monkeypatch, fault)
    assert not run(cell(name))["correct"]


def test_sharded_exchange_left_out_fails(monkeypatch):
    c = cell("multipod512.sweep8.xla.4chip",
             lanes_axes={"sym_on": [0, 1], "k": [0.01, 0.1],
                         "tau": [0.1, 0.5]})
    assert run(c)["correct"]
    _sweep_fault(monkeypatch, one_chip_only)
    assert not run(c)["correct"]


def _online_fault(monkeypatch, fault):
    real = netsim.SimController.step

    def broken(self, action=None, n_ticks=None):
        pre = self.state
        state, obs = real(self, action, n_ticks)
        return fault(self, pre, state, obs)

    monkeypatch.setattr(netsim.SimController, "step", broken)


def test_online_sound_run_is_correct():
    assert run(cell("table1.online.kernel"))["correct"]


def test_online_state_unchanged_fails(monkeypatch):
    def fault(ctl, pre, state, obs):
        ctl.state = pre
        return pre, obs
    _online_fault(monkeypatch, fault)
    assert not run(cell("table1.online.kernel"))["correct"]


def test_online_answer_altered_fails(monkeypatch):
    def fault(ctl, pre, state, obs):
        t = np.asarray(obs.samples.ts_throughput).copy()
        t[-1] *= 1.01
        return state, obs._replace(
            samples=obs.samples._replace(ts_throughput=jnp.asarray(t)))
    _online_fault(monkeypatch, fault)
    assert not run(cell("table1.online.kernel"))["correct"]


class _Control:
    """The reference in bfloat16 in the program's place."""

    def readings(self):
        return self.control_readings(jnp.bfloat16)


class SweepControl(_Control, Sweep):
    pass


class OnlineControl(_Control, Online):
    pass


@pytest.mark.parametrize("name", SWEEPS[:2])
def test_sweep_control_fails(name):
    assert not run(cell(name), kind_cls=SweepControl)["correct"]


def test_online_control_fails():
    assert not run(cell("table1.online.kernel"),
                   kind_cls=OnlineControl)["correct"]
