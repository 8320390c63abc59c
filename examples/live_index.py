"""Live corpus demo: incremental ingest, delta plans, standing queries.

    PYTHONPATH=src python examples/live_index.py \
        [--n 96] [--l 48] [--steps 4] [--k 5]

The batch examples compute against a frozen corpus; this demo shows the
live shape (ISSUE 9): the corpus keeps growing and changing while two
standing consumers stay current without ever recomputing from scratch —

  * a :class:`~repro.serving.live.LiveIndex` maintaining the corpus'
    own all-pairs top-k neighbour table, and
  * a :class:`~repro.serving.server.CorrServer` ``watch()`` — a standing
    probes-vs-corpus top-k query that pushes refreshed results to a
    callback whenever a delta lands.

Each ``append(d rows)`` re-transforms only the d new rows (Welford
moment maintenance) and launches only the d-vs-n grid plus the d-vs-d
triangle — not the full (n+d)-row triangle.  Each ``update`` merges the
changed rows into the running moments and recomputes exactly the stale
slices.  After every mutation the maintained results are checked against
a cold ``corr()`` over the current snapshot, and every result names the
corpus generation it answered against.
"""

import argparse

import numpy as np

from repro.core.api import corr
from repro.core.sinks import TopKSink
from repro.serving import CorrServer, DRIFT_TOL, LiveIndex

T, LBLK = 16, 16


def check_topk(tag, got_idx, got_val, want, k):
    """Maintained top-k vs a cold TopKSink run over the same snapshot."""
    w_idx = np.asarray(want["indices"])[:, :k]
    w_val = np.asarray(want["values"])[:, :k]
    assert np.array_equal(np.asarray(got_idx), w_idx), f"{tag}: indices drifted"
    err = float(np.max(np.abs(np.asarray(got_val) - w_val)))
    assert err <= DRIFT_TOL, f"{tag}: |dvalue| {err:.2e} > {DRIFT_TOL}"
    return err


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=96, help="initial corpus rows")
    ap.add_argument("--l", type=int, default=48, help="samples per row")
    ap.add_argument("--steps", type=int, default=4,
                    help="mutation cycles (append then update per cycle)")
    ap.add_argument("--k", type=int, default=5,
                    help="top-K strongest |r| partners per row")
    args = ap.parse_args()

    rng = np.random.default_rng(7)
    x = rng.standard_normal((args.n, args.l)).astype(np.float32)
    probes = rng.standard_normal((3, args.l)).astype(np.float32)

    pushes = []

    with CorrServer(x, t=T, l_blk=LBLK, max_wait_s=0.0) as srv, \
            LiveIndex(srv.corpus, measure="pearson", k=args.k) as index:
        watch = srv.watch(probes, args.k,
                          callback=lambda snap: pushes.append(snap))

        d = max(1, args.n // 16)
        for step in range(args.steps):
            # -- append d brand-new rows (delta grid + delta triangle) -----
            new = rng.standard_normal((d, args.l)).astype(np.float32)
            delta = srv.corpus.append(new)
            x = np.concatenate([x, new])

            # -- update d existing rows in place (moment merge) ------------
            idx = rng.choice(x.shape[0], size=d, replace=False)
            repl = rng.standard_normal((d, args.l)).astype(np.float32)
            srv.corpus.update(idx, repl)
            x[np.sort(idx)] = repl[np.argsort(idx)]

            # -- both standing consumers must match a cold recompute -------
            cold = corr(x, t=T, l_blk=LBLK, sink=TopKSink(args.k))
            live = index.result()
            err_i = check_topk(f"index step {step}", live["indices"],
                               live["values"], cold, args.k)

            cold_w = corr(probes, x, t=T, l_blk=LBLK, sink=TopKSink(args.k))
            snap = watch.current()
            err_w = check_topk(f"watch step {step}", snap["indices"],
                               snap["values"], cold_w, args.k)

            gen = srv.corpus.generation
            assert live["generation"] == snap["generation"] == gen
            print(f"step {step}: gen {delta.generation}->{gen} "
                  f"n={x.shape[0]}  index |dr|<={err_i:.1e}  "
                  f"watch |dr|<={err_w:.1e}  pushes={len(pushes)}")

        st = srv.corpus.stats()
        ist = index.stats()
        print(f"\ncorpus: n={st['rows']} generation={st['generation']} "
              f"refreshes={st['refreshes']} drift_budget={st['drift_budget']}")
        for key, live_st in st["live"].items():
            print(f"  maintained operand {key}: "
                  f"update_batches={live_st['update_batches']}")
        print(f"index: generation={ist['generation']} (k={args.k})")
        print(f"watch: generation={watch.generation} "
              f"pushes={len(pushes)} (pushed only when the top-k changed)")
        print("\nall standing results matched cold corr() at every step; "
              "every answer named the corpus generation it was computed "
              "against.")


if __name__ == "__main__":
    main()
