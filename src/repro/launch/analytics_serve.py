"""Analytics serving front-end: a multi-session demo loop over
:class:`~repro.core.AnalyticsServer`.

``python -m repro.launch.analytics_serve`` stands up one server and N
simulated analyst sessions issuing rounds of same-table statements
(profile / linregr / count-min / FM) from concurrent threads, with a
configurable append-ingest cadence racing the admission window.  It
prints per-round serving telemetry — statements, physical scans, dedup
and cache-hit counts, scans saved — straight from the server's trace
events, i.e. the in-database serving story of the paper (§3.2) made
observable: many analysts, one scan.  ``--drain=thread`` switches to
the production posture: the server's background drainer fires the
admission windows on ``--window-timeout`` and the analyst threads wait
passively on their handles instead of flushing.

This is the analytics sibling of :mod:`repro.launch.serve` (LM decode);
see :mod:`repro.core.server` for the admission-window and cache
contracts, and ``benchmarks/bench_serve.py`` for the measured version.
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from ..core import AnalyticsServer, Session, Table, trace_execution
from . import compile_cache


def _make_table(rows: int, dims: int, seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((rows, dims), dtype=np.float32)
    b = rng.standard_normal(dims, dtype=np.float32)
    y = (x @ b + 0.1 * rng.standard_normal(rows, dtype=np.float32))
    return Table.from_columns({
        "x": x, "y": y.astype(np.float32),
        "item": rng.integers(0, 1000, rows).astype(np.int32)})


def _analyst_round(session: Session, table: Table,
                   passive: bool = False) -> list:
    hs = [session.profile(table), session.linregr(table),
          session.countmin_sketch(table), session.fm_distinct_count(table)]
    if passive:
        # drain="thread": wait for the background drainer to fire the
        # window — nothing on this thread ever demands a flush, so the
        # subsequent run() only gathers already-resolved handles
        for h in hs:
            if hasattr(h, "wait"):
                assert h.wait(60), "background drainer never fired"
    return session.run()


def serve_analytics(*, rows: int = 100_000, dims: int = 8,
                    sessions: int = 8, rounds: int = 4,
                    window_size: int = 64, drain: str = "demand",
                    window_timeout: float | None = None,
                    append_every: int = 2, seed: int = 0) -> dict:
    """Run the demo loop; returns the final server stats dict.

    ``drain="thread"`` exercises the background drainer: every analyst
    thread submits its round and then waits PASSIVELY on its handles
    (no demand flush) — the server's own drain thread fires the windows
    on ``window_timeout``, the production serving posture."""
    table = _make_table(rows, dims, seed)
    rng = np.random.default_rng(seed + 1)
    if drain == "thread" and window_timeout is None:
        window_timeout = 0.01
    server = AnalyticsServer(window_size=window_size, drain=drain,
                             window_timeout=window_timeout)
    pool = [Session(server=server) for _ in range(sessions)]
    passive = drain == "thread"

    for rnd in range(rounds):
        if append_every and rnd and rnd % append_every == 0:
            m = max(1, rows // 200)
            table.append({
                "x": rng.standard_normal((m, dims)).astype(np.float32),
                "y": rng.standard_normal(m).astype(np.float32),
                "item": rng.integers(0, 1000, m).astype(np.int32)})
            print(f"round {rnd}: ingest +{m} rows -> cache evicted "
                  f"(total {server.stats['evicted']})")
        errors: list = [None] * sessions

        def analyst(i: int) -> None:
            try:
                _analyst_round(pool[i], table, passive)
            except BaseException as e:  # re-raised on the main thread below
                errors[i] = e

        with trace_execution() as t:
            t0 = time.perf_counter()
            threads = [threading.Thread(target=analyst, args=(i,))
                       for i in range(sessions)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
        failed = [e for e in errors if e is not None]
        if failed:
            server.close()
            raise RuntimeError(
                f"round {rnd}: {len(failed)} of {sessions} analyst sessions "
                "failed") from failed[0]
        summ = t.summary()
        stmts = sessions * 4
        print(f"round {rnd}: {sessions} sessions x 4 statements | "
              f"scans={summ.get('scan', 0)} "
              f"cache_hits={summ.get('cache_hit', 0)} "
              f"deduped={summ.get('deduped', 0)} "
              f"scans_saved={summ.get('scans_saved', 0)} | "
              f"{stmts / dt:.0f} stmts/s")
    stats = dict(server.stats)
    server.close()
    print(f"lifetime: {stats}")
    return stats


def main():
    ap = argparse.ArgumentParser(
        description="analytics serving demo: N sessions, one scan")
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--dims", type=int, default=8)
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--window-size", type=int, default=64)
    ap.add_argument("--drain", choices=("demand", "thread"),
                    default="demand",
                    help="'thread' = background drainer; analysts wait "
                         "passively instead of flushing")
    ap.add_argument("--window-timeout", type=float, default=None,
                    help="window age (s) that auto-drains; defaults to "
                         "0.01 with --drain=thread")
    ap.add_argument("--append-every", type=int, default=2,
                    help="ingest a delta every K rounds (0 = never)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()
    serve_analytics(rows=args.rows, dims=args.dims,
                    sessions=args.sessions, rounds=args.rounds,
                    window_size=args.window_size, drain=args.drain,
                    window_timeout=args.window_timeout,
                    append_every=args.append_every, seed=args.seed)


if __name__ == "__main__":
    main()
