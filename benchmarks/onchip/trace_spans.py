#!/usr/bin/env python3
"""Run one cell traced, exactly as `run.py --trace 1` does, and reduce the
same trace for the serving engine's own spans as well.

    python3 benchmarks/onchip/trace_spans.py --workload <name> --seed <n> \
        --seconds <s> [--keep trace.xplane.pb] [--tiny]

Prints the run's result line, then one JSON line: per engine span its
calls, wall, device busy and self idle seconds and counter sums
(`onchip_bench/spans.py`), the per-layer numbers they give, and the
device's idle seconds by engine span and runtime event.  `--keep` copies
the trace, and `--tiny` runs the workload from the tiny copy of the
benchmark that the CPU tests use (`tinykit.py`, e.g.
`tiny-lm.tiny_offline`).  Needs the chip, as `run.py` does.  Fails if
`run.py` no longer reduces its trace through `xtrace.reduce`.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

import run
from onchip_bench import spans, xtrace

#: runtime events listed under each engine span
TOP_EVENTS = 6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--keep")
    ap.add_argument("--tiny", action="store_true")
    args, rest = ap.parse_known_args(argv)
    kw = {}
    if args.tiny:
        from tinykit import make_tiny_root
        root = pathlib.Path(tempfile.mkdtemp(prefix="onchip-tiny-"))
        kw = {"root": root, "here": make_tiny_root(root)}

    seen = {}
    reduce = xtrace.reduce

    def reduce_both(path):
        out = reduce(path)
        busy, lines, host, window = spans.load(path)
        by_span = spans.reduce_lines(busy, lines)
        owners = spans.gap_owners(busy, lines, host, window)
        seen.update(spans=by_span, per_layer=spans.per_layer(by_span),
                    idle_by_span={
                        s: dict(sorted(ev.items(), key=lambda kv: -kv[1])
                                [:TOP_EVENTS])
                        for s, ev in owners.items()})
        if args.keep:
            shutil.copy(path, args.keep)
        return out

    xtrace.reduce = reduce_both
    try:
        rc = run.main(rest + ["--trace", "1"], **kw)
    finally:
        xtrace.reduce = reduce
    if rc == 0 and not seen:
        # run.py no longer reduces its trace through `xtrace.reduce`
        print("trace_spans: run.py never called xtrace.reduce",
              file=sys.stderr)
        return 1
    if rc == 0:
        print(json.dumps(seen), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
