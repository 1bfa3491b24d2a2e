"""Merge downloaded ``BENCH_smoke.json`` artifacts into a trajectory table.

Every CI run uploads its machine-readable benchmark record as the
``BENCH_smoke`` artifact (see ``.github/workflows/ci.yml``).  Download a
set of them (e.g. with ``gh run download -n BENCH_smoke -D artifacts/<id>``
per run) and merge:

    python -m benchmarks.collect_history artifacts/*/BENCH_smoke.json \
        [--out history.md] [--csv history.csv] [--png history.png]

Records are sorted by their ``generated_unix`` stamp; one row per record,
one column per streaming config's deterministic ops/step (the gated
metric), with max_wait, wall-clock, per-config compile time and the
fleet compile-amortization factor riding along.  Missing configs (older
records predate r32/W=2, schema<3 records predate the fleet section)
render as ``-`` — the table is the union, so the trajectory stays
readable across config-set changes.

``--png`` renders the same trajectory as a two-panel plot (ops/step and
compile seconds per config over time) via matplotlib; when matplotlib is
not installed the flag degrades to a warning so the minimal CI
environment can still run the merge.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List


def load_records(paths: List[str]) -> List[dict]:
    recs = []
    for path in paths:
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"skipping {path}: {e}", file=sys.stderr)
            continue
        if "streaming" not in rec:
            print(f"skipping {path}: no streaming section", file=sys.stderr)
            continue
        rec["_path"] = path
        recs.append(rec)
    recs.sort(key=lambda r: r.get("generated_unix", 0))
    return recs


def config_keys(recs: List[dict]) -> List[str]:
    """Union of streaming config keys, width-1 configs first."""
    keys = {k for r in recs for k in r["streaming"]}
    return sorted(keys, key=lambda k: ("_w" in k, k))


def _stamp(rec: dict) -> str:
    t = rec.get("generated_unix")
    return time.strftime("%Y-%m-%d %H:%M", time.gmtime(t)) if t else "?"


def _fleet_amort(rec: dict):
    return rec.get("fleet", {}).get("compile", {}).get("amortization_x")


def to_markdown(recs: List[dict]) -> str:
    keys = config_keys(recs)
    head = (["date (UTC)", "jax"]
            + [f"{k} ops/step" for k in keys]
            + [f"{k} max_wait" for k in keys]
            + [f"{k} compile_s" for k in keys]
            + ["fleet amort x"])
    lines = ["| " + " | ".join(head) + " |",
             "|" + "---|" * len(head)]
    for rec in recs:
        row = [_stamp(rec), rec.get("jax_version", "?")]
        for field, fmt in (("ops_per_step", "{:.4f}"), ("max_wait", "{}"),
                           ("compile_s", "{}")):
            for k in keys:
                cfg = rec["streaming"].get(k)
                row.append(fmt.format(cfg[field]) if cfg and field in cfg
                           else "-")
        v = _fleet_amort(rec)
        row.append("-" if v is None else f"{v}")
        lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines) + "\n"


def to_csv(recs: List[dict]) -> str:
    keys = config_keys(recs)
    head = (["generated_unix", "jax_version"]
            + [f"{k}_ops_per_step" for k in keys]
            + [f"{k}_max_wait" for k in keys]
            + [f"{k}_wall_s" for k in keys]
            + [f"{k}_compile_s" for k in keys]
            + ["fleet_amortization_x"])
    rows = [",".join(head)]
    for rec in recs:
        row = [str(rec.get("generated_unix", "")),
               rec.get("jax_version", "")]
        for field in ("ops_per_step", "max_wait", "wall_s", "compile_s"):
            for k in keys:
                cfg = rec["streaming"].get(k)
                row.append(str(cfg[field]) if cfg and field in cfg else "")
        v = _fleet_amort(rec)
        row.append("" if v is None else str(v))
        rows.append(",".join(row))
    return "\n".join(rows) + "\n"


def to_png(recs: List[dict], path: str) -> bool:
    """Render the trajectory as a two-panel PNG (ops/step + compile_s).

    matplotlib is an OPTIONAL dependency: returns False (after a
    stderr warning) when it is missing, so the minimal CI environment
    can still run the markdown/CSV merge."""
    try:
        import matplotlib
        matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except ImportError:
        print("matplotlib not installed; skipping PNG render",
              file=sys.stderr)
        return False

    keys = config_keys(recs)
    stamps = [_stamp(r) for r in recs]
    x = range(len(recs))
    fig, (ax_ops, ax_cmp) = plt.subplots(
        2, 1, figsize=(max(8, 1.2 * len(recs) + 4), 8), sharex=True)
    for k in keys:
        ops = [r["streaming"].get(k, {}).get("ops_per_step") for r in recs]
        cmp_ = [r["streaming"].get(k, {}).get("compile_s") for r in recs]
        ax_ops.plot(x, ops, marker="o", label=k)
        ax_cmp.plot(x, cmp_, marker="o", label=k)
    amort = [_fleet_amort(r) for r in recs]
    if any(a is not None for a in amort):
        ax_amort = ax_cmp.twinx()
        ax_amort.plot(x, amort, marker="s", color="black", linestyle="--",
                      label="fleet amort x")
        ax_amort.set_ylabel("fleet compile amortization (x)")
        ax_amort.legend(loc="upper right", fontsize=8)
    ax_ops.set_ylabel("ops/step (gated)")
    ax_ops.legend(loc="best", fontsize=8, ncol=2)
    ax_ops.grid(True, alpha=0.3)
    ax_cmp.set_ylabel("compile_s (informational)")
    ax_cmp.grid(True, alpha=0.3)
    ax_cmp.set_xticks(list(x))
    ax_cmp.set_xticklabels(stamps, rotation=30, ha="right", fontsize=8)
    fig.suptitle("bench_smoke trajectory")
    fig.tight_layout()
    fig.savefig(path, dpi=120)
    plt.close(fig)
    return True


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("records", nargs="+",
                    help="BENCH_smoke.json files (downloaded artifacts "
                         "and/or the committed baseline)")
    ap.add_argument("--out", default=None,
                    help="write the markdown table here (default: stdout)")
    ap.add_argument("--csv", default=None,
                    help="also write a machine-readable CSV here")
    ap.add_argument("--png", default=None,
                    help="also render the trajectory plot here (needs "
                         "matplotlib; skipped with a warning otherwise)")
    args = ap.parse_args()

    recs = load_records(args.records)
    if not recs:
        raise SystemExit("no readable benchmark records")
    md = to_markdown(recs)
    if args.out:
        with open(args.out, "w") as f:
            f.write(md)
        print(f"wrote {args.out} ({len(recs)} records)")
    else:
        print(md, end="")
    if args.csv:
        with open(args.csv, "w") as f:
            f.write(to_csv(recs))
        print(f"wrote {args.csv}")
    if args.png:
        if to_png(recs, args.png):
            print(f"wrote {args.png}")


if __name__ == "__main__":
    main()
