"""The paper's end-to-end deployment flow on a complete network:
extract per-operator workloads from BERT-tiny (the paper's NLP benchmark)
and tune them as one TuningSession — unique workloads deduped, searches
warm-started from any existing database records, one shared trial budget —
then report the network-level latency against the hand-written library
mapping: Figure 7's experiment.

Run:  python examples/tune_workload.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import nets
from repro.core import AnalyticRunner, TuningDatabase, TuningSession, V5E


def main() -> None:
    ops = nets.bert_tiny(dtype="int8")
    db = TuningDatabase()
    session = TuningSession(V5E, AnalyticRunner(V5E), database=db, log=print)
    result = session.tune_model(ops, total_trials=32 * len(ops), seed=0)

    print(f"\n{'operator':44s} {'tuned':>10s} {'library':>10s}  speedup")
    for rep in result.reports:
        print(f"{rep.workload.key():44s} {rep.best_latency * 1e6:9.2f}us "
              f"{rep.fixed_latency * 1e6:9.2f}us  "
              f"{rep.speedup_vs_fixed:6.2f}x  (x{rep.count})")

    t_tuned, t_fixed = result.tuned_latency, result.fixed_latency
    print(f"\nbert-tiny total: tuned {t_tuned * 1e6:.1f} us, "
          f"library {t_fixed * 1e6:.1f} us "
          f"-> {(1 - t_tuned / t_fixed) * 100:.0f}% latency improvement")
    # The analytic runner measures instantaneously, so this session runs
    # serially; on an overlap-capable runner (InterpretRunner) the session
    # interleaves one workload's measurement with another's evolution and
    # reports the hidden fraction here.
    print(f"session wall time: {result.wall_time_s:.1f}s "
          f"(interleaved={result.interleaved}, "
          f"overlap {result.overlap_fraction:.0%})")
    print(f"database records: {len(db)}, session summaries: "
          f"{len(db.sessions)}")


if __name__ == "__main__":
    main()
