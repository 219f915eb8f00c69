"""Cross-run perf-regression ledger: BENCH_*.json -> BENCH_TRAJECTORY.json.

The repo accumulates one benchmark artifact per measured round —
`BENCH_SERVE_r0N.json` (serving rows, one dict per row keyed by the
row's `key`) and `BENCH_r0N.json` (the training north-star line,
`parsed` out of bench.py's stdout) — and until ISSUE 13 nothing READ
them: the bench trajectory was a pile of unread JSON, and a PR that
slowed a row down produced no signal anywhere.

This module is the reader:

- `build_trajectory(root)` ingests every artifact under `root` into a
  schema-validated `BENCH_TRAJECTORY.json`: per-row, per-metric series
  keyed by row name, each entry carrying the round, source file, date,
  value/unit, and the BACKEND it was measured on (the CPU-backend
  caveat rides every entry, not a footnote — cross-backend points are
  never pooled into one noise band).
- `classify(trajectory, rows)` is the comparison gate: each of the
  latest run's rows is classified against the same-backend noise band
  of its prior series — `ok` / `improved` / `regressed` / `new` /
  `insufficient_history` — with the regression direction taken from
  the unit (`ms/...` = lower-better inverted).
- `check_latest(root)` runs the gate over the most recent serve round
  and returns a nonzero exit code on any regression — the loud signal
  `dstpu_bench --history --check` and future PRs get instead of silent
  drift.

Malformed artifacts raise `LedgerError` naming the file and the field
(the tier-1 ledger-schema gate in tests/test_observatory.py runs this
validation over every committed artifact, so a bad BENCH_*.json fails
at commit time rather than silently dropping out of the trajectory).

Noise-band model, deliberately simple: the band of a row's prior
same-backend values is [min, max] widened by `rel_tol` on each side.
`rel_tol` defaults to 0.35 — this container's serve rows are
documented (bench_serve.py RECORDED notes) to swing +-30% run to run
on the shared host, and a band tighter than the measured noise would
cry wolf.  Rows measured once get the same tolerance around their
single point.  Hardware-stable environments should pass a tighter
`--tol`.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["LedgerError", "SCHEMA_VERSION", "TRAJECTORY_FILE",
           "discover_artifacts", "load_serve_artifact",
           "load_train_artifact", "build_trajectory",
           "validate_trajectory", "write_trajectory", "load_trajectory",
           "rebuild", "classify", "check_latest", "main"]

SCHEMA_VERSION = 1
TRAJECTORY_FILE = "BENCH_TRAJECTORY.json"
DEFAULT_REL_TOL = 0.35

#: units where LOWER is better (everything else: higher is better)
_LOWER_BETTER = re.compile(r"^ms(/|$)|^s(/|$)|latency", re.IGNORECASE)


class LedgerError(ValueError):
    """A malformed benchmark artifact or trajectory (names the file)."""


def _require(cond: bool, path: str, msg: str) -> None:
    if not cond:
        raise LedgerError(f"{path}: {msg}")


def discover_artifacts(root: str) -> Tuple[List[str], List[str]]:
    """(serve_files, train_files) under `root`, round order."""
    def ordered(pattern: str) -> List[str]:
        return sorted(glob.glob(os.path.join(root, pattern)))

    return ordered("BENCH_SERVE_r*.json"), ordered("BENCH_r*.json")


def load_serve_artifact(path: str) -> Dict[str, Any]:
    """Parse + schema-validate one BENCH_SERVE_r0N.json."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise LedgerError(f"{path}: not valid JSON ({e})") from e
    _require(isinstance(doc, dict), path, "top level must be an object")
    for key, typ in (("round", int), ("date", str), ("backend", str),
                     ("rows", list)):
        _require(key in doc, path, f"missing required field {key!r}")
        _require(isinstance(doc[key], typ), path,
                 f"field {key!r} must be {typ.__name__}, got "
                 f"{type(doc[key]).__name__}")
    for i, row in enumerate(doc["rows"]):
        _require(isinstance(row, dict), path, f"rows[{i}] must be an "
                 f"object")
        _require(isinstance(row.get("key"), str) and row["key"], path,
                 f"rows[{i}] missing its row 'key'")
        _require(isinstance(row.get("value"), (int, float)), path,
                 f"rows[{i}] ({row.get('key')}): 'value' must be a "
                 f"number, got {row.get('value')!r}")
        _require(isinstance(row.get("unit"), str) and row["unit"], path,
                 f"rows[{i}] ({row.get('key')}): missing 'unit'")
    return doc


def load_train_artifact(path: str) -> Dict[str, Any]:
    """Parse + schema-validate one BENCH_r0N.json (bench.py's wrapped
    north-star line)."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except json.JSONDecodeError as e:
        raise LedgerError(f"{path}: not valid JSON ({e})") from e
    _require(isinstance(doc, dict), path, "top level must be an object")
    _require(isinstance(doc.get("n"), int), path,
             "missing integer field 'n' (the round number)")
    parsed = doc.get("parsed")
    _require(isinstance(parsed, dict), path,
             "missing 'parsed' object (bench.py's JSON line)")
    _require(isinstance(parsed.get("metric"), str) and parsed["metric"],
             path, "parsed.metric must be a non-empty string")
    _require(isinstance(parsed.get("value"), (int, float)), path,
             f"parsed.value must be a number, got "
             f"{parsed.get('value')!r}")
    _require(isinstance(parsed.get("unit"), str) and parsed["unit"],
             path, "parsed.unit must be a non-empty string")
    return doc


def build_trajectory(root: str) -> Dict[str, Any]:
    """Ingest every artifact under `root` into one trajectory doc.

    Serve rows key their series by the row's `key`; train artifacts key
    by the full parsed metric string (the configuration is part of the
    name, so a model-scale change starts a NEW series instead of
    polluting the old one's noise band)."""
    serve_files, train_files = discover_artifacts(root)
    rows: Dict[str, Dict[str, Any]] = {}

    def series_for(name: str, unit: str, path: str) -> List[dict]:
        entry = rows.setdefault(name, {"unit": unit, "series": [],
                                       "backends": []})
        _require(entry["unit"] == unit, path,
                 f"row {name!r} changes unit {entry['unit']!r} -> "
                 f"{unit!r} mid-trajectory")
        return entry["series"]

    for path in serve_files:
        doc = load_serve_artifact(path)
        fname = os.path.basename(path)
        for row in doc["rows"]:
            series = series_for(row["key"], row["unit"], path)
            entry = {
                "round": doc["round"],
                "source": fname,
                "date": doc["date"],
                # the per-row backend caveat (ISSUE 13 satellite): rows
                # measured before the per-row stamp fall back to the
                # document-level backend
                "backend": row.get("backend", doc["backend"]),
                "value": float(row["value"]),
                "note": row.get("note") or doc.get("note") or "",
            }
            if doc.get("gate_failed"):
                # this round FAILED the regression gate when it was
                # measured (persist_rows stamps the artifact before
                # raising): its values are excluded from future noise
                # bands, so an unfixed regression keeps failing instead
                # of self-healing into the band after one loud round
                entry["gate_failed"] = True
            series.append(entry)
    for path in train_files:
        doc = load_train_artifact(path)
        parsed = doc["parsed"]
        series = series_for(parsed["metric"], parsed["unit"], path)
        series.append({
            "round": doc["n"],
            "source": os.path.basename(path),
            "date": "",
            # bench.py rounds carry no backend stamp of their own
            "backend": str(doc.get("backend", "unknown")),
            "value": float(parsed["value"]),
            "note": "",
        })
    for name, entry in rows.items():
        entry["series"].sort(key=lambda e: (e["round"], e["source"]))
        entry["backends"] = sorted({e["backend"]
                                    for e in entry["series"]})
    doc = {
        "schema_version": SCHEMA_VERSION,
        "sources": {
            "serve": [os.path.basename(p) for p in serve_files],
            "train": [os.path.basename(p) for p in train_files],
        },
        "rows": rows,
    }
    validate_trajectory(doc, path="<built>")
    return doc


def validate_trajectory(doc: Dict[str, Any],
                        path: str = TRAJECTORY_FILE) -> None:
    _require(isinstance(doc, dict), path, "top level must be an object")
    _require(doc.get("schema_version") == SCHEMA_VERSION, path,
             f"schema_version must be {SCHEMA_VERSION}, got "
             f"{doc.get('schema_version')!r}")
    _require(isinstance(doc.get("sources"), dict), path,
             "missing 'sources' object")
    _require(isinstance(doc.get("rows"), dict), path,
             "missing 'rows' object")
    for name, entry in doc["rows"].items():
        _require(isinstance(entry, dict), path,
                 f"rows[{name!r}] must be an object")
        _require(isinstance(entry.get("unit"), str) and entry["unit"],
                 path, f"rows[{name!r}] missing 'unit'")
        series = entry.get("series")
        _require(isinstance(series, list) and series, path,
                 f"rows[{name!r}] needs a non-empty 'series'")
        for i, e in enumerate(series):
            for key, typ in (("round", int), ("source", str),
                             ("backend", str), ("value", (int, float))):
                _require(isinstance(e.get(key), typ), path,
                         f"rows[{name!r}].series[{i}] field {key!r} "
                         f"must be {typ}, got {e.get(key)!r}")


def write_trajectory(doc: Dict[str, Any], root: str) -> str:
    path = os.path.join(root, TRAJECTORY_FILE)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=False)
        f.write("\n")
    return path


def load_trajectory(root: str) -> Dict[str, Any]:
    path = os.path.join(root, TRAJECTORY_FILE)
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except FileNotFoundError:
        raise LedgerError(
            f"{path}: no trajectory — build one with "
            f"`dstpu_bench --history --rebuild`")
    except json.JSONDecodeError as e:
        raise LedgerError(f"{path}: not valid JSON ({e})") from e
    validate_trajectory(doc, path)
    return doc


def mark_gate_failed(artifact_path: str) -> None:
    """Stamp one serve artifact as having FAILED the regression gate
    (bench_serve's persist_rows calls this before raising).  The stamp
    rides into the trajectory on the next rebuild, and `classify`
    excludes stamped rounds from every future noise band — so an
    unfixed regression keeps failing the gate on re-runs instead of
    becoming its own precedent.  Clearing the stamp (an accepted
    perf change) is an explicit hand edit of the artifact."""
    doc = load_serve_artifact(artifact_path)
    doc["gate_failed"] = True
    with open(artifact_path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def rebuild(root: str) -> str:
    """Rebuild BENCH_TRAJECTORY.json from every artifact under `root`
    (idempotent — this is also how bench_serve.py auto-appends: write
    the new round's artifact, rebuild the trajectory)."""
    return write_trajectory(build_trajectory(root), root)


# -- the comparison gate ---------------------------------------------------

def lower_is_better(unit: str) -> bool:
    return bool(_LOWER_BETTER.search(unit))


def noise_band(values: List[float], rel_tol: float
               ) -> Tuple[float, float]:
    lo, hi = min(values), max(values)
    return lo - abs(lo) * rel_tol, hi + abs(hi) * rel_tol


def classify(trajectory: Dict[str, Any], rows: List[Dict[str, Any]],
             backend: str, rel_tol: float = DEFAULT_REL_TOL,
             exclude_sources: Tuple[str, ...] = ()) -> List[dict]:
    """Classify each latest-run row against its trajectory series.

    `rows`: [{key, value, unit, backend?}, ...] (a bench_serve round's
    rows); a row-level `backend` overrides the document-level default —
    the same per-row caveat the trajectory entries carry, so a partial
    round re-measured on different hardware is classified against ITS
    band, never the document's.
    `exclude_sources`: artifact filenames whose entries must not count
    as history (the round being checked, when it is already ingested).
    Verdicts: `new` (no same-backend history), `unit_mismatch` (the
    row changed unit — no comparison is possible, which the GATE
    treats as a failure, not a pass); a single prior point still
    yields a band (the tolerance covers it) but is flagged
    `thin_history=True`; `regressed` / `improved` / `ok` otherwise."""
    out: List[dict] = []
    for row in rows:
        name, value, unit = row["key"], float(row["value"]), row["unit"]
        row_backend = str(row.get("backend", backend))
        entry = trajectory["rows"].get(name)
        # gate-failed rounds never count as history: a regressed value
        # must not widen the band its own unfixed re-run is judged by
        prior = [e for e in (entry or {}).get("series", ())
                 if e["backend"] == row_backend
                 and e["source"] not in exclude_sources
                 and not e.get("gate_failed")]
        rec: Dict[str, Any] = {"row": name, "value": value,
                               "unit": unit, "backend": row_backend,
                               "prior_points": len(prior)}
        if entry is not None and entry["unit"] != unit:
            rec.update(verdict="unit_mismatch",
                       detail=f"trajectory unit {entry['unit']!r}")
            out.append(rec)
            continue
        if not prior:
            rec["verdict"] = "new"
            out.append(rec)
            continue
        values = [e["value"] for e in prior]
        lo, hi = noise_band(values, rel_tol)
        rec["band"] = [lo, hi]
        rec["thin_history"] = len(prior) < 2
        if lower_is_better(unit):
            worse, better = value > hi, value < lo
        else:
            worse, better = value < lo, value > hi
        rec["verdict"] = ("regressed" if worse
                          else "improved" if better else "ok")
        out.append(rec)
    return out


def check_latest(root: str, rel_tol: float = DEFAULT_REL_TOL
                 ) -> Tuple[List[dict], int]:
    """Gate the most recent serve round against the rest of the
    trajectory.  Returns (report, exit_code): nonzero iff any row
    regressed OR changed unit — a `unit_mismatch` row was never
    compared at all, so letting it pass would hide a real regression
    behind a unit rename.  (A malformed ledger raises.)  Rows carry
    their own backend stamp when present, so a mixed-hardware partial
    round classifies each row against ITS backend's band."""
    serve_files, _ = discover_artifacts(root)
    if not serve_files:
        raise LedgerError(
            f"{root}: no BENCH_SERVE_r*.json artifacts to check")
    latest = serve_files[-1]
    doc = load_serve_artifact(latest)
    trajectory = load_trajectory(root)
    report = classify(
        trajectory,
        [{"key": r["key"], "value": r["value"], "unit": r["unit"],
          "backend": r.get("backend", doc["backend"])}
         for r in doc["rows"]],
        backend=doc["backend"], rel_tol=rel_tol,
        exclude_sources=(os.path.basename(latest),))
    code = 1 if any(r["verdict"] in ("regressed", "unit_mismatch")
                    for r in report) else 0
    return report, code


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        "bench_history",
        description="perf-regression ledger over BENCH_*.json artifacts "
                    "(also reachable as `dstpu_bench --history`)")
    p.add_argument("--root", default=".",
                   help="directory holding the BENCH_*.json artifacts")
    p.add_argument("--rebuild", action="store_true",
                   help="rebuild BENCH_TRAJECTORY.json from every "
                        "artifact")
    p.add_argument("--check", action="store_true",
                   help="classify the latest serve round against the "
                        "trajectory's noise band; exit 1 on regression")
    p.add_argument("--tol", type=float, default=DEFAULT_REL_TOL,
                   help="relative noise-band tolerance (default "
                        f"{DEFAULT_REL_TOL} — this container's measured "
                        "run-to-run swing)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output (one JSON line per "
                        "row)")
    args = p.parse_args(argv)
    if not args.rebuild and not args.check:
        p.error("nothing to do: pass --rebuild and/or --check")
    rc = 0
    if args.rebuild:
        path = rebuild(args.root)
        n_rows = len(load_trajectory(args.root)["rows"])
        print(json.dumps({"rebuilt": path, "rows": n_rows})
              if args.json else f"rebuilt {path} ({n_rows} row series)")
    if args.check:
        report, rc = check_latest(args.root, rel_tol=args.tol)
        for rec in report:
            if args.json:
                print(json.dumps(rec))
            else:
                band = rec.get("band")
                band_s = (f" band=[{band[0]:.2f}, {band[1]:.2f}]"
                          if band else "")
                print(f"{rec['verdict']:>12}  {rec['row']}: "
                      f"{rec['value']} {rec['unit']}"
                      f" ({rec['prior_points']} prior){band_s}")
        if rc:
            print("REGRESSION: at least one row fell outside its "
                  "trajectory noise band (or changed unit and could "
                  "not be compared)")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
