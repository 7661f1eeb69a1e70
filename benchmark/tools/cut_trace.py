#!/usr/bin/env python3
"""Cut a small recorded trace out of a large one, for `tests/test_trace.py`.

    python3 benchmark/tools/cut_trace.py <xplane.pb | compact.json.gz> <out.json.gz> \
        --modules '^jit_decode:2' '^jit_prefill:1'

Keeps, for each `pattern:count`, the first `count` executions of the programs
whose name matches, with every operation inside them, shifted so that the
cut begins at 0; writes the compact form `xtrace.Trace.load` reads."""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xtrace  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--modules", nargs="+", required=True)
    args = ap.parse_args()
    tr = xtrace.Trace.load(args.src)
    keep = []
    for item in args.modules:
        pattern, count = item.rsplit(":", 1)
        rx = re.compile(pattern)
        hits = [(s, s + d) for n, s, d in tr.lanes[xtrace.MODULES] if rx.search(n)]
        keep += hits[1:1 + int(count)] or hits[:int(count)]
    keep.sort()
    base = keep[0][0]
    lanes = {k: [[xtrace.short_name(n), s - base, d] for n, s, d in v
                 if any(a <= s and s + d <= b for a, b in keep)]
             for k, v in tr.lanes.items()}
    with gzip.open(args.dst, "wt") as f:
        json.dump({"device": tr.device, "lanes": lanes,
                   "cut_from": os.path.basename(args.src), "kept": keep}, f)
    print({k: len(v) for k, v in lanes.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
