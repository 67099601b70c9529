"""Read ``chiprun_out/sets.<cell>.jsonl`` (benchmark/tools/measure_sets.sh):
per metric and set the median and the spread — the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a
share of the median — and the wider of the two sets' spreads.

    python benchmark/tools/read_sets.py chiprun_out/sets.<cell>.jsonl
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path):
    rows = [json.loads(line) for line in open(path)]
    sets = {}
    for r in rows:
        if r["set"] in "AB" and r["rc"] == 0:
            for name, m in r["line"]["metrics"].items():
                sets.setdefault(name, {}).setdefault(r["set"], []).append(
                    m["value"])
    print("correct:", [r["line"]["correct"] for r in rows if r["rc"] == 0],
          "rcs:", [r["rc"] for r in rows])
    for name, by_set in sets.items():
        out = {}
        for s, vals in sorted(by_set.items()):
            first, rest = vals[0], vals
            if name == "setup_s" and s == "A":
                rest = vals[1:]         # the first run compiles
            out[s] = {"median": statistics.median(rest),
                      "spread": spread(rest) if len(rest) >= 2 else None,
                      "n": len(rest)}
        wide = max(v["spread"] for v in out.values() if v["spread"] is not None)
        print(name, json.dumps(out), "wider spread %.4f -> bound ~%.4f"
              % (wide, 5 * wide))
    for r in rows:
        if r["set"] == "T" and r["rc"] == 0:
            print("traced:", json.dumps(r["line"])[:3000])


if __name__ == "__main__":
    main(sys.argv[1])
