"""Device: the share of the device's idle time in the traced span that a
stage of the scheduler's loop accounts for — the part of the gaps between
the device's busy intervals that lies under a `sched.*` span other than the
loop pass itself (`hostspans.idle_by_span`). The idle seconds by span name
go to stderr and, as `idle_by_span.json`, into the run's directory beside
`run.log`. Open loop only: a closed loop's idle time is microseconds between
programs and the share says nothing there."""
import json
import os
import sys

import hostspans


def read(ctx):
    spans = hostspans.of(ctx)
    if not any(name.startswith("sched.") for name, *_ in spans):
        return None
    idle_s, under = hostspans.idle_by_span(ctx.trace, spans)
    by = {"idle_s": idle_s, "under_no_span_s": idle_s - sum(under.values()),
          "by_span_s": dict(sorted(under.items(), key=lambda kv: -kv[1]))}
    print(f"device idle in the traced span, by host span: {json.dumps(by)}",
          file=sys.stderr, flush=True)
    run_dir = hostspans.run_dir(ctx.trace)
    if run_dir:
        with open(os.path.join(run_dir, "idle_by_span.json"), "w") as f:
            json.dump(by, f)
    return 100.0 * sum(under.values()) / idle_s if idle_s > 0 else None
