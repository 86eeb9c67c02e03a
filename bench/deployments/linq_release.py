"""Linear-query release deployments (``configs/linq-*.json``).

Builds the data from the seed on the device, the `ReleaseService` the
configuration names, and its tenants; offers the load generator's target
interface; and, once the window has closed, compares what the timed path
produced with the plain reference beside this file (`linq_reference`).

Data is the paper's §5.1 synthetic workload, generated here so the
inputs stay fixed whatever the program does: ``m`` 0/1 query rows, each
marking ``U/4`` draws from N(U/2, U/5), and per tenant a histogram of
``n_records`` draws from N(U/3, U/15) over ``[0, U)``. The histograms
count by sort and search instead of a scatter-add; the counts are the
same integers.
"""

from __future__ import annotations

import os
import tempfile
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.deployments import linq_reference as ref

# The route every kernel of the timed path must take on the chip.
EXPECTED_ROUTE = "compiled"
# Steps of each release, drawn from the seed, at which the selection and
# the probe are compared with float64.
CHECKED_STEPS = 8


def data_key(seed: int) -> jax.Array:
    """A key that keeps all of a large seed (PRNGKey alone keeps 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


@partial(jax.jit, static_argnames=("m", "U", "tenants", "n"))
def make_data(key, *, m: int, U: int, tenants: int, n: int):
    """(Q (m, U) f32 0/1, H (tenants, U) f32 normalised histograms)."""
    kq, kh = jax.random.split(key)
    pts = U / 2.0 + U / 5.0 * jax.random.normal(kq, (m, U // 4))
    idx = jnp.clip(jnp.round(pts).astype(jnp.int32), 0, U - 1)
    rows = jnp.broadcast_to(jnp.arange(m)[:, None], idx.shape)
    Q = jnp.zeros((m, U), jnp.float32).at[rows, idx].set(1.0)

    def hist(k):
        x = U / 3.0 + U / 15.0 * jax.random.normal(k, (n,))
        b = jnp.sort(jnp.clip(jnp.round(x).astype(jnp.int32), 0, U - 1))
        edges = jnp.searchsorted(b, jnp.arange(U + 1), side="left")
        return (edges[1:] - edges[:-1]).astype(jnp.float32) / n

    return Q, jax.vmap(hist)(jax.random.split(kh, tenants))


class Deployment:
    """One service under one mix, ready for `bench.loadgen.drive`."""

    def __init__(self, cfg: dict, mix: dict, tenants, seed: int,
                 timings: dict):
        from repro.core import MWEMConfig
        from repro.serve.coalesce import DeadlineOccupancyPolicy, WaveLadder
        from repro.serve.journal import Journal
        from repro.serve.release_service import ReleaseService

        self.cfg, self.mix, self.tenants = cfg, mix, list(tenants)
        self.m, self.U, self.T = cfg["m"] // 2, cfg["U"], cfg["T"]
        self.k = max(1, int(np.ceil(np.sqrt(self.m))))
        self.at = np.sort(np.random.default_rng(
            [int(seed) % 2**63, 0x73746570]).choice(
                self.T, min(CHECKED_STEPS, self.T), replace=False))
        self._probe_fn = None
        t0 = time.perf_counter()
        self.Q, H = make_data(data_key(seed), m=self.m, U=self.U,
                              tenants=len(self.tenants), n=cfg["n_records"])
        # the clients' own copy of the query rows, for reads and checks
        self.Q8 = np.asarray(self.Q.astype(jnp.uint8))
        self.H = np.asarray(H)
        timings["data_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self._dir = tempfile.mkdtemp(prefix="linq-")
        self.journal = Journal(os.path.join(self._dir, "wal.jsonl"),
                               fsync=cfg["journal_fsync"])
        ladder = WaveLadder(tuple(mix["ladder"]))
        self.svc = ReleaseService(
            self.Q, MWEMConfig(eps=cfg["eps"], delta=cfg["delta"], T=self.T,
                               mode=cfg["mode"],
                               update_rule=cfg["update_rule"]),
            wave_size=cfg["wave_size"], index_kind=cfg["index_kind"],
            seed=int(seed) % 2**30, streaming=True, journal=self.journal,
            policy=DeadlineOccupancyPolicy(wave_size=cfg["wave_size"],
                                           ladder=ladder))
        self._check_index()
        budget = mix["budget"]
        for tid, h in zip(self.tenants, self.H):
            self.svc.create_session(tid, eps_budget=budget["eps"],
                                    delta_budget=budget["delta"], h=h,
                                    n_records=cfg["n_records"])
        # the wave's per-lane selections, which the service does not keep
        self.selected = {}
        deliver = self.svc._deliver_mwem

        def tap(wave, result, trigger=None):
            sel = np.asarray(result.selected)
            for i, t in enumerate(wave):
                self.selected[t.ticket_id] = sel[i]
            return deliver(wave, result, trigger=trigger)

        self.svc._deliver_mwem = tap
        timings["index_s"] = time.perf_counter() - t0

    def _check_index(self):
        want = self.cfg.get("index") or {}
        idx = self.svc.index
        got = {"nlist": getattr(idx, "nlist", None),
               "cap": getattr(idx, "cap", None),
               "nprobe": getattr(idx, "nprobe", None),
               "k": self.k}
        bad = {k: (got[k], v) for k, v in want.items() if got.get(k) != v}
        if bad:
            raise ValueError(f"index differs from the configuration: {bad}")

    def warm(self, timings: dict) -> None:
        t0 = time.perf_counter()
        self.svc.prewarm(self.cfg["n_records"])
        timings["compile_s"] = time.perf_counter() - t0

    # ---------------------------------------------------- load-gen target
    @property
    def n_rows(self) -> int:
        return self.m

    def submit(self, tenant):
        return self.svc.submit(tenant)

    @staticmethod
    def state(ticket) -> str:
        if ticket.status in ("queued", "retrying"):
            return "pending"
        return "done" if ticket.status == "done" else "failed"

    def pump(self):
        self.svc.pump()

    def released(self, tenant) -> bool:
        return bool(self.svc.sessions[tenant].releases)

    def read(self, tenant, row):
        ans = self.svc.answer(tenant, self.Q8[row].astype(np.float32))
        return ans.value, ans.release_id

    def probe(self, V) -> tuple:
        """The service's own index probe, the wave's call at the wave's
        width, on the vectors ``V`` (B, U): ((B, k) augmented ids, (B, k)
        scores)."""
        from repro.mips.base import bind_state, index_state

        idx = self.svc.index
        if self._probe_fn is None:
            self._probe_fn = jax.jit(
                lambda st, V: bind_state(idx, st).query_in_graph_batch(
                    V, self.k))
        ids, raw = self._probe_fn(index_state(idx), jnp.asarray(V))
        return np.asarray(ids), np.asarray(raw)

    # ------------------------------------------------------------ checks
    def snapshot(self, record) -> dict:
        """What the checks need from the service, taken before it is
        freed: the window's releases, selections, ledgers and routes; and
        the probe's answers at the reference's replayed states of the
        checked steps (the probe needs the program's index, so the f32
        replay runs here, after the memory peak was read)."""
        from repro.kernels import route

        done = [r.handle for r in record.in_window() if r.status == "done"]
        ledger_gap = 0.0
        for tid in self.tenants:
            sess = self.svc.sessions[tid]
            mine = [t for t in (r.handle for r in record.releases)
                    if t.tenant_id == tid and t.decision.admitted]
            if not mine:
                continue
            now = sess.ledger.preview(*sess.ledger.reserved_bundle())
            proj = (mine[-1].decision.eps_projected,
                    mine[-1].decision.delta_projected)
            ledger_gap = max(ledger_gap, abs(now[0] - proj[0]),
                             abs(now[1] - proj[1]))
        p_by_id = {rel.release_id: np.asarray(rel.p_hat, np.float32)
                   for tid in self.tenants
                   for rel in self.svc.sessions[tid].releases}
        taken = route.taken()
        off = sum(1 for k in self.cfg.get("kernels", [])
                  if taken.get(k) != {EXPECTED_ROUTE})
        off += sum(1 for v in taken.values() if v != {EXPECTED_ROUTE})
        snap = {
            "tickets": done,
            "selected": [self.selected.get(t.ticket_id) for t in done],
            "h": [self.H[self.tenants.index(t.tenant_id)] for t in done],
            "p_by_id": p_by_id,
            "ledger_gap": ledger_gap,
            "routes_off": off,
            "routes": {k: sorted(v) for k, v in taken.items()},
            "breaker_trips": max(self.svc.breaker.trips,
                                 int(self.svc.degraded)),
        }
        sel = snap["selected"]
        if not done or any(s is None for s in sel):
            return snap
        sel = np.stack(sel).astype(np.int32)
        if sel.min() < 0 or sel.max() >= self.m:
            return snap
        cfg, H = self.cfg, np.stack(snap["h"])
        nz = ref.noise([t.seed for t in done], self.T,
                       ref.lap_scale(cfg["eps"], cfg["delta"], self.T,
                                     cfg["n_records"]))
        P_ref, seen = ref.replay(self.Q, H, sel, nz, self.at)
        V = (H[:, None, :] - seen).astype(np.float32)          # (R, S, U)
        R, S, _ = V.shape
        B = cfg["wave_size"]
        ids = np.zeros((R, S, self.k), np.int64)
        raw = np.zeros((R, S, self.k), np.float32)
        for i in range(0, R, B):
            lanes = np.arange(i, i + B) % R      # a short last wave wraps
            for j in range(S):
                got = self.probe(V[lanes, j])
                n = min(B, R - i)
                ids[i:i + n, j], raw[i:i + n, j] = got[0][:n], got[1][:n]
        snap.update(sel=sel, P_ref=P_ref, V=V, probe_ids=ids, probe_raw=raw)
        return snap

    def free(self) -> None:
        """Drop the service and its index (the program's device state)."""
        self.journal.close()
        for name in os.listdir(self._dir):
            os.remove(os.path.join(self._dir, name))
        os.rmdir(self._dir)
        self.svc = None
        self._probe_fn = None

    def check(self, snap: dict, record) -> dict:
        """Each number compared with the reference: {name: value}. The
        configuration's limits name those compared; the rest (the top-k
        recall; on an approximate index, the selection gap) are printed."""
        cfg = self.cfg
        out = {"ledger_gap": snap["ledger_gap"],
               "kernel_routes_off": float(snap["routes_off"]),
               "breaker_trips": float(snap["breaker_trips"])}
        reads = [r for r in record.window_reads() if r.ok]
        if reads:
            want = ref.answers64(self.Q8, [r.row for r in reads],
                                 [r.release_id for r in reads],
                                 snap["p_by_id"])
            got = np.asarray([r.value for r in reads])
            out["answer_gap"] = float(np.max(
                np.abs(got - want) / (ref.F32_REL * want + 1e-30)))
        tickets = snap["tickets"]
        if not tickets:
            return out
        P = np.stack([np.asarray(t.release.p_hat, np.float32)
                      for t in tickets])
        H = np.stack(snap["h"])
        fe = np.asarray([t.final_error for t in tickets])
        err64, scale64 = ref.release_errors64(self.Q8, P, H)
        out["final_error_gap"] = float(np.max(
            np.abs(fe - err64) / (ref.F32_REL * scale64 + 1e-30)))
        if "P_ref" not in snap:         # a selection missing or invalid
            for k in ("release_replay_gap", "selection_gap",
                      "probe_score_gap", "topk_boundary_gap"):
                out[k] = float("inf")
            return out
        P_ref = snap["P_ref"]
        out["release_replay_gap"] = float(np.max(
            np.max(np.abs(P - P_ref), axis=1) / np.max(P_ref, axis=1)))
        V = snap["V"]
        R, S, U = V.shape
        S64, M64 = ref.scores64(self.Q8, V.reshape(R * S, U))
        out["selection_gap"] = float(np.max(ref.selection_gaps(
            S64, snap["sel"][:, self.at].reshape(-1),
            ref.em_scale(cfg["eps"], cfg["delta"], self.T,
                         cfg["n_records"]))))
        ids = snap["probe_ids"].reshape(R * S, -1)
        out["probe_score_gap"] = float(np.max(ref.probe_gaps(
            S64, M64, ids, snap["probe_raw"].reshape(R * S, -1))))
        out["topk_boundary_gap"] = float(np.max(ref.topk_boundary_gaps(
            S64, M64, ids)))
        recall = ref.topk_recall(S64, ids)
        out["probe_topk_recall"] = float(np.min(recall))
        out["probe_topk_recall_mean"] = float(np.mean(recall))
        return out
