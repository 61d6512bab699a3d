"""Analytics serving: cross-session admission-window sharing + caching.

N analysts hitting one table used to pay N independent executions — even
with each analyst's own batch perfectly fused (PR 5), the table is
scanned once PER SESSION.  The :class:`~repro.core.AnalyticsServer`
admission window plans across sessions: all compatible statements in a
window fuse into ONE pass, identical statements deduplicate to one
member, and repeats against an unchanged table are answered from the
version-keyed result cache with ZERO scans.  Four sections, with scans
counted by :func:`repro.core.trace_execution` (engine events, not
guesses):

* **solo** (baseline) — every session plans and runs its OWN prepared
  batch: ``sessions`` fused passes per round.
* **served** — the same statements submitted by concurrent sessions into
  one admission window, result cache cleared between rounds so the
  number measures window fusion + dedup alone: ONE fused pass per round.
* **cached** — the server round WITHOUT clearing: every statement hits
  the version-keyed cache, zero scans, and the JSON records
  ``bit_identical`` against a fresh solo execution (exact-state
  aggregates only — integer sketches and deterministic f32 folds).
* **mutation** — ``Table.append`` between rounds: eviction hooks drop
  the dead entries and the next round replans (scans back to one),
  results matching a fresh solo run over the grown table bitwise.
* **isolation** — per-table admission windows under the BACKGROUND
  drainer (``drain="thread"``): a deterministically slow statement on
  table A (an eager transition that sleeps) executes while table B's
  statements drain on their own worker.  Overlap and B's drain latency
  come from the per-table ``admission`` trace events' monotonic
  timestamps — never from wall-clock heuristics around the round.

``--drain=thread`` runs the served/cached sections against a
background-drainer server (submitters wait passively on their handles;
the server's own thread fires the windows), measuring the production
serving posture; the default ``demand`` drains on ``flush()`` as
before.  ``--smoke`` asserts the structural claims
(scans-per-statement <= 1/N submitters; cached rounds execute zero
scans with bit-identical results; B's drains overlap A's slow
statement) and is wired into CI with the JSON uploaded as an artifact;
the full run also reports served-vs-solo statement throughput (the
>=3x serving win on scan-dominated batches).
"""

from __future__ import annotations

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AnalyticsServer, ProfileAggregate, ScanAgg, Session, Table, execute,
    trace_execution,
)
from repro.core.aggregates import MERGE_SUM, Aggregate
from repro.methods.linregr import LinregrAggregate
from repro.methods.sketches import CountMinAggregate, FMAggregate


def _columns(rows: int, dims: int) -> dict:
    rng = np.random.default_rng(0)
    x = rng.standard_normal((rows, dims), dtype=np.float32)
    b = rng.standard_normal(dims, dtype=np.float32)
    y = (x @ b + 0.1 * rng.standard_normal(rows, dtype=np.float32))
    return {"x": x, "y": y.astype(np.float32),
            "item": rng.integers(0, 1000, rows).astype(np.int32)}


def _statements(table: Table, block_size: int) -> list:
    """One analyst's statement set — profile / linregr / two sketches —
    as prebuilt nodes (prepared statements, so steady-state rounds
    measure execution, not trace+compile)."""
    return [
        ScanAgg(ProfileAggregate(), table, columns=("x", "y"),
                block_size=block_size, label="profile"),
        ScanAgg(LinregrAggregate(), table, columns={"x": "x", "y": "y"},
                block_size=block_size, label="linregr"),
        ScanAgg(CountMinAggregate(4, 1024, item_col="item"), table,
                columns=("item",), block_size=block_size, label="countmin"),
        ScanAgg(FMAggregate(item_col="item"), table, columns=("item",),
                block_size=block_size, label="fm"),
    ]


def _block_on(results) -> None:
    for leaf in jax.tree.leaves(results):
        jax.block_until_ready(leaf)


def _bitwise_equal(a, b) -> bool:
    fa = [np.asarray(x) for x in jax.tree.leaves(a)]
    fb = [np.asarray(x) for x in jax.tree.leaves(b)]
    return len(fa) == len(fb) and all(
        x.shape == y.shape and (x == y).all() for x, y in zip(fa, fb))


class _SleepAggregate(Aggregate):
    """Deterministically slow scan: the transition sleeps on the host.
    Run eagerly (``jit=False``, unblocked -> ONE Python-level call), the
    sleep genuinely occupies the executing drain worker for
    ``seconds`` — the isolation section's 'slow statement on table A'."""

    merge_ops = MERGE_SUM

    def __init__(self, seconds: float):
        self.seconds = seconds

    def init(self, block):
        return jnp.zeros((), dtype=jnp.float32)

    def transition(self, state, block, mask):
        time.sleep(self.seconds)
        return state + jnp.sum(jnp.where(mask, block["y"], 0.0))


def _served_round(server: AnalyticsServer, batches: list[list],
                  passive: bool = False) -> list:
    """All sessions submit concurrently into ONE window, then one drain;
    returns every statement's result.  ``passive`` (the drain-thread
    axis) waits on the handles instead of flushing — the server's own
    drainer fires the window."""
    sessions = [Session(server=server) for _ in batches]
    out: list = [None] * len(batches)
    handles: list = []

    def submit(i):
        for node in batches[i]:
            handles.append(sessions[i].statement(node))

    threads = [threading.Thread(target=submit, args=(i,))
               for i in range(len(batches))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if passive:
        for h in handles:
            assert h.wait(60), "background drainer never fired"
    else:
        server.flush()
    for i, s in enumerate(sessions):
        out[i] = s.run()       # window already drained; gathers handles
    return [r for batch in out for r in batch]


def _time_rounds(fn, reps: int):
    """(min seconds over reps, scans in last round) after one untimed
    warmup round (compile)."""
    fn()
    best = float("inf")
    scans = 0
    for _ in range(reps):
        with trace_execution() as t:
            t0 = time.perf_counter()
            out = fn()
            _block_on(out)
            best = min(best, time.perf_counter() - t0)
        scans = len(t.scans)
    return best, scans


def bench(rows: int = 200_000, dims: int = 8, sessions: int = 8,
          reps: int = 3, block_size: int = 4096,
          drain: str = "demand") -> dict:
    cols = _columns(rows, dims)
    table = Table.from_columns(cols)
    n_stmts = sessions * 4
    out: dict = {"config": {"rows": rows, "dims": dims,
                            "sessions": sessions, "reps": reps,
                            "block_size": block_size, "drain": drain,
                            "statements": n_stmts}}

    # -- solo baseline: each session fuses ITS OWN batch, pays its own scan
    solo_batches = [_statements(table, block_size) for _ in range(sessions)]

    def solo_round():
        res = []
        for batch in solo_batches:
            sess = Session()
            for node in batch:
                sess.statement(node)
            res.extend(sess.run())
        return res

    solo_s, solo_scans = _time_rounds(solo_round, reps)
    solo_results = solo_round()
    out["solo"] = {"seconds": solo_s, "scans": solo_scans,
                   "stmts_per_sec": n_stmts / solo_s}

    # -- served: one admission window across all sessions, cache cleared
    passive = drain == "thread"
    server = AnalyticsServer(
        window_size=4 * n_stmts, drain=drain,
        window_timeout=0.01 if passive else None)
    served_batches = [_statements(table, block_size)
                      for _ in range(sessions)]

    def served_round():
        server.clear_cache()
        return _served_round(server, served_batches, passive)

    served_s, served_scans = _time_rounds(served_round, reps)
    out["served"] = {"seconds": served_s, "scans": served_scans,
                     "stmts_per_sec": n_stmts / served_s,
                     "scans_per_statement": served_scans / n_stmts}
    out["speedup"] = solo_s / served_s

    # -- cached: the same window again WITHOUT clearing ------------------
    _served_round(server, served_batches)  # warm the cache
    with trace_execution() as t:
        t0 = time.perf_counter()
        cached_results = _served_round(server, served_batches)
        _block_on(cached_results)
        cached_s = time.perf_counter() - t0
    out["cached"] = {
        "seconds": cached_s, "scans": len(t.scans),
        "cache_hits": len(t.cache_hits),
        "stmts_per_sec": n_stmts / cached_s,
        "bit_identical": _bitwise_equal(cached_results, solo_results),
        "speedup_vs_solo": solo_s / cached_s,
    }

    # -- mutation: append evicts, the next window replans ----------------
    delta = {k: v[: max(1, rows // 100)] for k, v in cols.items()}
    table.append(delta)
    evicted = server.stats["evicted"]
    with trace_execution() as t:
        post_results = _served_round(server, served_batches)
        _block_on(post_results)
    fresh = [execute(node) for node in _statements(table, block_size)]
    out["mutation"] = {
        "evicted": evicted, "scans": len(t.scans),
        "cache_hits": len(t.cache_hits),
        "bit_identical_to_fresh": _bitwise_equal(
            post_results[: len(fresh)], fresh),
    }
    out["server_stats"] = dict(server.stats)
    server.close()

    out["isolation"] = _isolation_section(rows, dims, block_size)
    return out


def _isolation_section(rows: int, dims: int, block_size: int,
                       slow_seconds: float = 0.5) -> dict:
    """Per-table window isolation under the background drainer: while
    table A's drain worker is stuck in a deterministically slow
    statement, table B's statements drain on their own worker.  Overlap
    and latency are read off the per-table ``admission`` trace events
    (monotonic ``opened_at``/``drained_at``), plus one structural check:
    every B handle resolved while A's was still pending."""
    ta = Table.from_columns(_columns(max(rows // 4, 1000), dims))
    tb = Table.from_columns(_columns(max(rows // 4, 1000), dims))
    # warm compile caches so B's drain time measures serving, not XLA
    _block_on([execute(n) for n in _statements(tb, block_size)])
    srv = AnalyticsServer(window_size=1, drain="thread")
    try:
        with trace_execution() as t:
            ha = srv.submit(ScanAgg(
                _SleepAggregate(slow_seconds), ta, columns=("y",),
                engine="local", jit=False, label="slow"))
            time.sleep(0.05)            # let A's worker enter the sleep
            s = Session(server=srv)
            hbs = [s.statement(n) for n in _statements(tb, block_size)]
            for h in hbs:
                assert h.wait(60), "table B starved behind table A"
            overlapped = not ha.done()  # B finished while A still ran
            assert ha.wait(60)
        a_evs = [e.detail for e in t.admissions
                 if e.detail["table"] == id(ta)]
        b_evs = [e.detail for e in t.admissions
                 if e.detail["table"] == id(tb)]
        return {
            "slow_exec_seconds": slow_seconds,
            "a_windows": len(a_evs),
            "b_windows": len(b_evs),
            "b_latency_max": max(e["latency"] for e in b_evs),
            "b_last_drained_before_a_done": (
                max(e["drained_at"] for e in b_evs)
                < a_evs[0]["drained_at"] + slow_seconds),
            "overlapped": overlapped,
        }
    finally:
        srv.close()


def check_smoke(doc: dict) -> None:
    """The structural serving claims, asserted from trace-counted scans —
    CI fails loudly if cross-session sharing or caching regresses."""
    n_sessions = doc["config"]["sessions"]
    served = doc["served"]
    assert served["scans_per_statement"] <= 1.0 / n_sessions, (
        f"window fusion regressed: {served['scans_per_statement']:.3f} "
        f"scans/statement with {n_sessions} submitters (want <= "
        f"{1.0 / n_sessions:.3f})")
    assert served["scans"] >= 1, "served round executed nothing"
    cached = doc["cached"]
    assert cached["scans"] == 0, (
        f"cached round executed {cached['scans']} scans (want 0)")
    assert cached["bit_identical"], (
        "cached results are not bit-identical to solo execution")
    mut = doc["mutation"]
    assert mut["evicted"] >= 1, "append evicted nothing"
    assert mut["scans"] >= 1, "post-mutation round served stale cache"
    assert mut["bit_identical_to_fresh"], (
        "post-mutation results do not match a fresh run")
    iso = doc["isolation"]
    assert iso["overlapped"], (
        "per-table isolation regressed: table B's statements waited out "
        "table A's slow drain")
    assert iso["b_last_drained_before_a_done"], (
        "table B's drains were queued behind table A's slow statement "
        "(admission timestamps)")
    assert iso["b_latency_max"] < iso["slow_exec_seconds"], (
        f"table B drain latency {iso['b_latency_max']:.3f}s approaches "
        f"table A's {iso['slow_exec_seconds']}s execution — windows are "
        "not isolated")


def run(rows: int = 200_000, reps: int = 3):
    """CSV rows for benchmarks/run.py: (name, us_per_call, derived)."""
    r = bench(rows=rows, reps=reps)
    return [
        ("serve_solo_8x4stmt", r["solo"]["seconds"] * 1e6,
         f"scans={r['solo']['scans']}"),
        ("serve_served_8x4stmt", r["served"]["seconds"] * 1e6,
         f"scans={r['served']['scans']}"),
        ("serve_speedup", r["speedup"], ""),
        ("serve_cached_8x4stmt", r["cached"]["seconds"] * 1e6,
         f"hits={r['cached']['cache_hits']} "
         f"bitident={r['cached']['bit_identical']}"),
        ("serve_cached_speedup", r["cached"]["speedup_vs_solo"], ""),
    ]


if __name__ == "__main__":
    import argparse

    from repro.launch import compile_cache

    compile_cache.enable()

    ap = argparse.ArgumentParser()
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the JSON document here (default: stdout)")
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block-size", type=int, default=4096)
    ap.add_argument("--drain", choices=("demand", "thread"),
                    default="demand",
                    help="'thread' = served/cached sections run against "
                         "the background drainer (passive submitters)")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + assert the structural claims")
    args = ap.parse_args()
    if args.smoke:
        args.rows = min(args.rows, 20_000)
        args.reps = min(args.reps, 2)
    doc = bench(rows=args.rows, dims=args.dims, sessions=args.sessions,
                reps=args.reps, block_size=args.block_size,
                drain=args.drain)
    if args.smoke:
        check_smoke(doc)
        doc["smoke"] = "ok"
    text = json.dumps(doc, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"wrote {args.json}")
    else:
        print(text)
