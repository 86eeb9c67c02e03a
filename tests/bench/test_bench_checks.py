"""The comparison that decides ``correct``, at a size a CPU holds.

Skips the harness's look for a chip and drives the rest of a run: a sound
run is correct; with the timed path broken underneath — a step that
returns its state unchanged, half of each wave left out, a selection
altered where the wave produces it, an answer altered where it is read,
a probe that returns the wrong rows or rounds its vector to bf16, a
service that fell back to its reference route — and with the plain
reference in the program's place one precision step down (the
control), it is not. One chip, so no exchange between chips exists to
leave out.
"""

import numpy as np
import pytest

from bench import control, harness

TINY = dict(U=256, m=1024, T=12, n_records=10000, kernels=[], index={})
CELLS = [c["name"] for c in harness.load_spec()["workloads"]]
LIMITS = harness.resolve(harness.load_spec(), CELLS[0])[1]["limits"]


@pytest.fixture(autouse=True)
def fresh_route_record():
    """A run reads the routes its process traced; tests that ran before in
    this worker traced kernels on the interpret route."""
    from repro.kernels import route

    route.reset()
    yield
    route.reset()


def run(cell, seed=2**31 + 5):
    res = control.readings(cell, [seed], 1.0, None, require_chip=False,
                           overrides=TINY, say=lambda s: None)
    return res[seed]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    got = run(cell)
    assert got["correct"], got


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.core import mwem

    monkeypatch.setattr(mwem, "mwem_step_ref",
                        lambda lw, p, ps, q, h, nz, **kw: (lw, p, ps))
    got = run(CELLS[0])
    assert not got["correct"] and got["release_replay_gap"] > 0.5


def _patch_results(monkeypatch, edit):
    from repro.serve import release_service as rs

    finish = rs.finish_mwem_batch

    def broken(pending, ledgers=None):
        res = finish(pending, ledgers=ledgers)
        edit(res)
        return res

    monkeypatch.setattr(rs, "finish_mwem_batch", broken)


def test_half_of_each_wave_left_out(monkeypatch):
    def half(res):
        p, sel = np.array(res.p_hat), np.array(res.selected)
        b = len(p) // 2
        p[b:], sel[b:] = p[:len(p) - b], sel[:len(p) - b]
        res.p_hat, res.selected = p, sel

    _patch_results(monkeypatch, half)
    got = run(CELLS[0])
    assert not got["correct"]
    assert got["release_replay_gap"] > LIMITS["release_replay_gap"]


def test_selection_altered_where_produced(monkeypatch):
    def alter(res):
        sel = np.array(res.selected)
        sel[:, 3] = (sel[:, 3] + 1) % (TINY["m"] // 2)
        res.selected = sel

    _patch_results(monkeypatch, alter)
    got = run(CELLS[0])
    assert not got["correct"]
    assert got["release_replay_gap"] > LIMITS["release_replay_gap"]


def test_answer_altered_where_read(monkeypatch):
    from repro.serve.session import Answer, TenantSession

    answer = TenantSession.answer

    def off(self, q, release_id=None):
        a = answer(self, q, release_id)
        return Answer(a.value * (1 + 1e-4), a.cached, a.release_id,
                      a.fingerprint)

    monkeypatch.setattr(TenantSession, "answer", off)
    got = run(CELLS[0])
    assert not got["correct"] and got["answer_gap"] > LIMITS["answer_gap"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_one_precision_step_down_is_not_correct(cell):
    res = control.readings(cell, [11], 1.0, "low", require_chip=False,
                           overrides=TINY, say=lambda s: None)
    assert not res[11]["correct"]
    # on the CPU the products stay f32, so bf16 arithmetic alone shows
    assert res[11]["release_replay_gap"] > LIMITS["release_replay_gap"]
    assert res[11]["answer_gap"] > LIMITS["answer_gap"]
    assert res[11]["probe_score_gap"] > LIMITS["probe_score_gap"]


def _patch_probe(monkeypatch, edit):
    """Break every index's wave probe, in the window and in the check."""
    from repro.mips.flat import FlatAbsIndex
    from repro.mips.ivf import IVFIndex

    for cls in (FlatAbsIndex, IVFIndex):
        probe = cls.query_in_graph_batch
        monkeypatch.setattr(
            cls, "query_in_graph_batch",
            lambda self, Vb, k, probe=probe: edit(self, probe, Vb, k))


def _wrong_rows(self, probe, Vb, k):
    ids, raw = probe(self, Vb, k)
    return (ids + 1) % TINY["m"], raw


def _bf16_vector(self, probe, Vb, k):
    import jax.numpy as jnp

    return probe(self, Vb.astype(jnp.bfloat16).astype(jnp.float32), k)


@pytest.mark.parametrize("edit", [_wrong_rows, _bf16_vector],
                         ids=["wrong_rows", "bf16_vector"])
@pytest.mark.parametrize("cell", CELLS)
def test_probe_broken(monkeypatch, cell, edit):
    _patch_probe(monkeypatch, edit)
    got = run(cell)
    assert not got["correct"]
    assert got["probe_score_gap"] > LIMITS["probe_score_gap"]


def test_service_degraded_to_its_reference_route(monkeypatch):
    """Three failed dispatches trip the breaker; the retried wave then
    runs on the XLA reference route and delivers, so only the breaker
    check can tell."""
    from repro.serve import release_service as rs

    launch, fails = rs.launch_mwem_batch, []

    def flaky(*args, **kw):
        if len(fails) < 3:
            fails.append(1)
            raise RuntimeError("injected dispatch failure")
        return launch(*args, **kw)

    monkeypatch.setattr(rs, "launch_mwem_batch", flaky)
    got = run(CELLS[0])
    assert len(fails) == 3
    assert not got["correct"] and got["breaker_trips"] > 0
    assert all(got[k] <= LIMITS[k] for k in LIMITS if k != "breaker_trips")
