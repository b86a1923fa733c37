"""Device idle ms per Trainer round inside the program's ``train.round``
host spans (``repro.obs`` spans are profiler annotations named by their
slash path), from the device trace and the host spans on its clock
(bench/spans.py). Each idle piece goes to the innermost span around it;
the split over ``train.round``'s children (``step``, ``sync``,
``record``, ...) and the harness's spans is printed on stderr."""
import sys
from pathlib import Path

from bench import spans

TRACE = Path(__file__).resolve().parents[1] / ".trace"
ROUND = "train.round"


def read(ctx):
    rounds = ctx["counters"].get("rounds")
    sp = spans.of_run(ctx, TRACE)
    if sp is None or not rounds or not sp.ops \
            or not any(n == ROUND for n, _, _ in sp.host):
        return None
    split = sp.idle_split(("bench.", ROUND))
    per_round = {k: 1e3 * v / rounds for k, v in split.items()}
    print("trainer_idle_ms: device idle ms per round by innermost span: "
          + ", ".join(f"{k or 'no span'} {v:.4f}" for k, v in
                      sorted(per_round.items(), key=lambda x: -x[1])),
          file=sys.stderr, flush=True)
    return sum(v for k, v in per_round.items()
               if k is not None and (k == ROUND or
                                     k.startswith(ROUND + "/")))
