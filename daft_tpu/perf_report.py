"""Performance observatory: benchmark trajectory store + span-diff reports.

The metrics plane answers "how much", the profiler answers "where did the
time go" — this module makes both DURABLE and COMPARABLE across commits, so
every perf claim ("q21 got 12% faster") is mechanically checkable instead of
anecdotal. Two pieces:

* **Trajectory store** — :func:`capture_query` runs one query under the
  profiler bracketed by a metrics-snapshot pair and distills a structured
  record: wall seconds, per-plan-node self wall/CPU from
  :meth:`~daft_tpu.profiling.QueryProfile.operator_table`, rows/bytes out,
  spill bytes, permit-wait, peak RSS, and the engine-counter deltas the
  query caused. :func:`build_entry` stamps a suite of records with the git
  SHA + host facts and :func:`append_entry` appends it to
  ``BENCH_TRAJECTORY.jsonl`` — one line per capture, append-only, diffable
  in git (the TPU-baseline studies' per-stage-utilization discipline
  applied to commits instead of chips).
* **Span-diff regression attribution** — :func:`diff_entries` compares any
  two trajectory entries (or two in-process captures via
  :func:`diff_records`) and ranks per-operator self-time deltas under each
  query's wall delta: ``q21 +12.0%: HashJoin#3 self +0.60s``. Cross-machine
  comparisons are CALIBRATED: the median per-query wall ratio is taken as
  the machines' speed difference, and each query is judged against that
  median — a box that is uniformly 2x slower flags nothing, a single query
  that slipped against its peers flags loudly.

Schema stability: entries carry ``schema_version``; :func:`validate_entry`
is the contract both the writer (scripts/perf_observatory.py) and the CI
gate check before trusting a line.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import threading
import time
from typing import Any, Callable, Dict, List, Optional

ENTRY_SCHEMA_VERSION = 1

#: Default trajectory location: the repo root, next to BENCH_TPCH.json.
TRAJECTORY_FILENAME = "BENCH_TRAJECTORY.jsonl"

_RECORD_REQUIRED = ("name", "wall_s", "rows_out", "operators", "metrics")
_OPERATOR_REQUIRED = ("operator", "self_wall_ns", "wall_ns", "rows")
_ENTRY_REQUIRED = ("schema_version", "sha", "captured_at", "suite", "host",
                   "queries", "total_wall_s", "peak_rss_bytes")


def default_trajectory_path() -> str:
    """``DAFT_TRAJECTORY_PATH`` override, else ``BENCH_TRAJECTORY.jsonl``
    next to this package's repo root."""
    from daft_tpu.config import daft_env

    override = daft_env("DAFT_TRAJECTORY_PATH")
    if override:
        return override
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, TRAJECTORY_FILENAME)


def git_sha(short: bool = True) -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short" if short else "--verify", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return ""


def peak_rss_bytes() -> int:
    """Peak resident set of THIS process so far (``ru_maxrss``; kilobytes on
    Linux, bytes on macOS). 0 where the resource module is unavailable."""
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss) if sys.platform == "darwin" else int(rss) * 1024
    except (ImportError, ValueError, OSError):
        return 0


def snapshot_delta(before, after) -> Dict[str, Any]:
    """Engine-counter deltas between two ``MetricsSnapshot``s: counters as
    total deltas, histograms as ``{count, sum}`` deltas; zero deltas and
    gauges (point-in-time, not attributable to the bracket) are dropped so
    records stay compact."""
    out: Dict[str, Any] = {}
    for name, m in after.raw.items():
        kind = m.get("kind")
        if kind == "counter":
            d = after.counter_total(name) - before.counter_total(name)
            if d:
                out[name] = round(d, 6)
        elif kind == "histogram":
            hb, ha = before.hist(name), after.hist(name)
            dc = ha["count"] - hb["count"]
            if dc:
                out[name] = {"count": round(dc, 6),
                             "sum": round(ha["sum"] - hb["sum"], 6)}
    return out


def _compact_operators(table: List[dict]) -> List[dict]:
    """Trajectory-ready operator rows: keep the attribution fields, drop
    always-zero optionals, round nothing (ns ints diff exactly)."""
    out = []
    for r in table:
        row = {"operator": r["operator"],
               "plan_node": r.get("plan_node", r["operator"]),
               "rows": r["rows"], "morsels": r["morsels"],
               "wall_ns": r["wall_ns"], "self_wall_ns": r["self_wall_ns"],
               "self_cpu_ns": r["self_cpu_ns"], "bytes_out": r["bytes_out"]}
        for opt in ("spill_bytes", "permit_wait_ns", "device_rows",
                    "fallback_rows"):
            if r.get(opt):
                row[opt] = r[opt]
        out.append(row)
    return out


def _root_rows(operators: List[dict]) -> int:
    """The query's output row count, read off the profiler's ROOT operator
    span (plan node ``…#0`` — the executor numbers nodes top-down) instead
    of ``len(df)``: a post-hoc ``count()`` derives a fresh plan and re-runs
    the query, which alone would blow the <2% recording budget."""
    for op in operators:
        if str(op.get("plan_node", "")).endswith("#0"):
            return int(op["rows"])
    return int(operators[0]["rows"]) if operators else 0


def capture_query(name: str, build: Callable[[], Any],
                  rounds: int = 1) -> dict:
    """Run ``build()`` (must return a LAZY DataFrame) under the profiler and
    a metrics-snapshot bracket; returns the trajectory record. ``rounds``
    repeats the capture and keeps the fastest wall (the min is the only
    estimator whose noise shrinks with samples; the profiler attribution
    kept is the winning round's)."""
    from daft_tpu.metrics import get_registry

    best: Optional[dict] = None
    for _ in range(max(rounds, 1)):
        reg = get_registry()
        before = reg.snapshot()
        t0 = time.perf_counter()
        df = build()
        df.collect(profile=True)
        wall = time.perf_counter() - t0
        after = reg.snapshot()
        prof = df.query_profile
        operators = _compact_operators(
            prof.operator_table(by="plan_node")) if prof else []
        rec = {
            "name": name,
            "wall_s": round(wall, 6),
            "rows_out": _root_rows(operators),
            "peak_rss_bytes": peak_rss_bytes(),
            "operators": operators,
            "metrics": snapshot_delta(before, after),
        }
        if best is None or rec["wall_s"] < best["wall_s"]:
            best = rec
    return best


def record_from_profile(name: str, profile, wall_s: float) -> dict:
    """A trajectory-shaped record from an already-finished QueryProfile —
    the in-process path into :func:`diff_records` (no store round-trip)."""
    return {"name": name, "wall_s": round(float(wall_s), 6),
            "rows_out": 0, "peak_rss_bytes": peak_rss_bytes(),
            "operators": _compact_operators(
                profile.operator_table(by="plan_node")),
            "metrics": {}}


def resolved_compute_threads() -> int:
    """The worker count the pipelined executor would actually use right
    now: the active config's ``num_compute_threads``, with 0 resolved to
    the visible core count (executor.py's rule)."""
    try:
        from daft_tpu.context import get_context

        n = get_context().execution_config.num_compute_threads
    except (ImportError, AttributeError):
        n = 0  # stamping must never fail a capture
    return n if n > 0 else (os.cpu_count() or 1)


def build_entry(suite: str, records: List[dict],
                config: Optional[dict] = None,
                sha: Optional[str] = None) -> dict:
    import platform

    return {
        "schema_version": ENTRY_SCHEMA_VERSION,
        "sha": sha if sha is not None else git_sha(),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "suite": suite,
        # cpu_cores + num_compute_threads make scaling claims auditable:
        # a trajectory diff between entries at different worker counts is
        # a configuration delta, not a code regression (the --cores sweep
        # in scripts/perf_observatory.py compares them deliberately).
        # cpu_cores is the canonical name going forward; cpu_count is the
        # legacy spelling kept so pre-existing entries stay comparable.
        "host": {"platform": platform.platform(),
                 "cpu_count": os.cpu_count() or 1,
                 "cpu_cores": os.cpu_count() or 1,
                 "num_compute_threads": resolved_compute_threads(),
                 "python": platform.python_version()},
        "config": dict(config or {}),
        "queries": records,
        "total_wall_s": round(sum(r["wall_s"] for r in records), 4),
        "peak_rss_bytes": peak_rss_bytes(),
    }


def validate_entry(entry: Any) -> List[str]:
    """Schema check for one trajectory entry; returns human-readable
    problems (empty = valid). Both the writer and the CI gate run this —
    a malformed line must fail loudly at write time, not at diff time."""
    errs: List[str] = []
    if not isinstance(entry, dict):
        return [f"entry is {type(entry).__name__}, not an object"]
    for key in _ENTRY_REQUIRED:
        if key not in entry:
            errs.append(f"missing key {key!r}")
    if errs:
        return errs
    if entry["schema_version"] != ENTRY_SCHEMA_VERSION:
        errs.append(f"schema_version {entry['schema_version']!r} != "
                    f"{ENTRY_SCHEMA_VERSION}")
    if not isinstance(entry["queries"], list) or not entry["queries"]:
        errs.append("queries must be a non-empty list")
        return errs
    for i, rec in enumerate(entry["queries"]):
        where = f"queries[{i}]"
        if not isinstance(rec, dict):
            errs.append(f"{where} is not an object")
            continue
        for key in _RECORD_REQUIRED:
            if key not in rec:
                errs.append(f"{where} missing {key!r}")
        if not isinstance(rec.get("wall_s"), (int, float)) \
                or rec.get("wall_s", -1) < 0:
            errs.append(f"{where}.wall_s must be a non-negative number")
        for j, op in enumerate(rec.get("operators") or []):
            for key in _OPERATOR_REQUIRED:
                if key not in op:
                    errs.append(f"{where}.operators[{j}] missing {key!r}")
    return errs


def append_entry(entry: dict, path: Optional[str] = None) -> str:
    """Validate + append one JSONL line; returns the path written."""
    errs = validate_entry(entry)
    if errs:
        from daft_tpu.errors import DaftValueError

        raise DaftValueError(
            "refusing to append schema-invalid trajectory entry: "
            + "; ".join(errs[:5]))
    path = path or default_trajectory_path()
    with open(path, "a") as f:
        f.write(json.dumps(entry, separators=(",", ":"), sort_keys=True)
                + "\n")
    return path


# Parsed-store cache keyed by (mtime_ns, size): the dashboard's Perf view
# polls the trajectory endpoints every second, and re-parsing a
# months-of-entries JSONL twice per tick on the single-threaded HTTP
# handler is the exact hazard the PR 6 timeline cache exists for. The
# store is append-only, so (mtime, size) identifies its content.
_traj_cache_lock = threading.Lock()
_TRAJ_CACHE: Dict[str, Any] = {}


def load_trajectory(path: Optional[str] = None,
                    suite: Optional[str] = None) -> List[dict]:
    """Every schema-valid entry in the store (oldest first), optionally
    filtered by suite. Invalid/corrupt lines are skipped, not fatal — a
    torn tail line must not take the whole trajectory down."""
    path = path or default_trajectory_path()
    try:
        st = os.stat(path)
    except OSError:
        return []
    key = (st.st_mtime_ns, st.st_size)
    with _traj_cache_lock:
        cached = _TRAJ_CACHE.get(path)
        entries = cached[1] if cached is not None and cached[0] == key \
            else None
    if entries is None:
        entries = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entry = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if validate_entry(entry):
                    continue
                entries.append(entry)
        with _traj_cache_lock:
            _TRAJ_CACHE[path] = (key, entries)
            # Bounded: the dashboard only ever reads one path; tests with
            # tmp files must not grow this forever.
            while len(_TRAJ_CACHE) > 8:
                _TRAJ_CACHE.pop(next(iter(_TRAJ_CACHE)))
    if suite is not None:
        return [e for e in entries if e.get("suite") == suite]
    return list(entries)


# --------------------------------------------------------------------- #
# Span-diff regression attribution                                      #
# --------------------------------------------------------------------- #
def _op_key(op: dict) -> str:
    return str(op.get("plan_node") or op.get("operator") or "?")


def diff_records(base: dict, cur: dict, calibration: float = 1.0) -> dict:
    """Per-query delta between two trajectory records, operator-attributed.

    ``calibration`` is the machines' median wall ratio (cur/base) over the
    whole suite: the *calibrated* percentage judges this query against its
    peers, so a uniformly slower box reads ~0% everywhere while a genuine
    per-query slip stands out."""
    base_wall, cur_wall = float(base["wall_s"]), float(cur["wall_s"])
    delta_pct = (cur_wall / base_wall - 1.0) * 100.0 if base_wall > 0 else 0.0
    expected = base_wall * calibration
    cal_pct = (cur_wall / expected - 1.0) * 100.0 if expected > 0 else 0.0
    base_ops = {_op_key(o): o for o in base.get("operators") or []}
    cur_ops = {_op_key(o): o for o in cur.get("operators") or []}
    op_deltas: List[dict] = []
    for key in set(base_ops) | set(cur_ops):
        b, c = base_ops.get(key), cur_ops.get(key)
        b_self = int(b["self_wall_ns"]) if b else 0
        c_self = int(c["self_wall_ns"]) if c else 0
        # Calibrate operator self-time the same way as walls so the ranked
        # attribution is machine-speed invariant too.
        delta_ns = c_self - int(b_self * calibration)
        op_deltas.append({
            "key": key,
            "operator": (c or b)["operator"],
            "status": ("changed" if b and c else
                       "added" if c else "removed"),
            "base_self_wall_ns": b_self, "cur_self_wall_ns": c_self,
            "delta_self_wall_ns": delta_ns,
            "base_rows": int(b["rows"]) if b else 0,
            "cur_rows": int(c["rows"]) if c else 0,
        })
    op_deltas.sort(key=lambda d: -abs(d["delta_self_wall_ns"]))
    return {"name": cur.get("name") or base.get("name"),
            "base_wall_s": base_wall, "cur_wall_s": cur_wall,
            "delta_s": round(cur_wall - base_wall, 6),
            "delta_pct": round(delta_pct, 2),
            "calibrated_pct": round(cal_pct, 2),
            "operators": op_deltas}


class RegressionReport:
    """Ranked per-query, per-operator delta report between two captures."""

    def __init__(self, base: dict, cur: dict, queries: List[dict],
                 calibration: float, only_in_base: List[str],
                 only_in_cur: List[str]):
        self.base_sha = base.get("sha", "")
        self.cur_sha = cur.get("sha", "")
        self.suite = cur.get("suite", base.get("suite", ""))
        self.calibration = calibration
        # Worst calibrated regression first.
        self.queries = sorted(queries,
                              key=lambda q: -q["calibrated_pct"])
        self.only_in_base = only_in_base
        self.only_in_cur = only_in_cur

    def regressions(self, threshold_pct: float = 20.0,
                    min_delta_s: float = 0.05) -> List[dict]:
        """Queries whose CALIBRATED slowdown clears both the relative
        threshold and an absolute floor (sub-50ms walls jitter more than
        they inform)."""
        return [q for q in self.queries
                if q["calibrated_pct"] >= threshold_pct
                and (q["cur_wall_s"] - q["base_wall_s"] *
                     self.calibration) >= min_delta_s]

    @staticmethod
    def headline(q: dict, top: int = 2) -> str:
        """``q21 +12.0%: HashJoin#3 self +0.60s; Filter#2 self +0.04s``."""
        sign = "+" if q["calibrated_pct"] >= 0 else ""
        parts = []
        for od in q["operators"][:top]:
            if od["delta_self_wall_ns"] == 0:
                continue
            s = od["delta_self_wall_ns"] / 1e9
            parts.append(f"{od['key']} self {s:+.2f}s"
                         + ("" if od["status"] == "changed"
                            else f" ({od['status']})"))
        attribution = "; ".join(parts) or "no operator attribution"
        return (f"{q['name']} {sign}{q['calibrated_pct']:.1f}%: "
                f"{attribution}")

    def to_json(self) -> dict:
        return {"base_sha": self.base_sha, "cur_sha": self.cur_sha,
                "suite": self.suite,
                "calibration": round(self.calibration, 4),
                "queries": self.queries,
                "only_in_base": self.only_in_base,
                "only_in_cur": self.only_in_cur}

    def format_table(self, top_operators: int = 2) -> str:
        names = ([q["name"] for q in self.queries]
                 + self.only_in_base + self.only_in_cur + ["query"])
        w = max(len(str(n)) for n in names)
        lines = [f"span-diff {self.base_sha or '?'} -> "
                 f"{self.cur_sha or '?'} (suite={self.suite}, "
                 f"calibration x{self.calibration:.3f})"]
        header = (f"{'query':<{w}} {'base':>9} {'cur':>9} {'delta':>9} "
                  f"{'cal%':>7}  top operator deltas")
        lines.append(header)
        lines.append("-" * len(header))
        for q in self.queries:
            tops = "; ".join(
                f"{od['key']} {od['delta_self_wall_ns'] / 1e9:+.3f}s"
                for od in q["operators"][:top_operators]
                if od["delta_self_wall_ns"])
            lines.append(
                f"{q['name']:<{w}} {q['base_wall_s']:>8.3f}s "
                f"{q['cur_wall_s']:>8.3f}s {q['delta_s']:>+8.3f}s "
                f"{q['calibrated_pct']:>+6.1f}%  {tops}")
        for name in self.only_in_cur:
            lines.append(f"{name:<{w}} {'-':>9} {'new':>9}")
        for name in self.only_in_base:
            lines.append(f"{name:<{w}} {'gone':>9} {'-':>9}")
        return "\n".join(lines)


def diff_entries(base: dict, cur: dict) -> RegressionReport:
    """Span-diff two trajectory entries (same suite, any two machines or
    commits): per-query wall deltas calibrated by the suite's median ratio,
    each attributed to ranked per-plan-node self-time deltas."""
    base_by = {r["name"]: r for r in base["queries"]}
    cur_by = {r["name"]: r for r in cur["queries"]}
    shared = [n for n in cur_by if n in base_by]
    ratios = [cur_by[n]["wall_s"] / base_by[n]["wall_s"]
              for n in shared if base_by[n]["wall_s"] > 0]
    calibration = statistics.median(ratios) if ratios else 1.0
    queries = [diff_records(base_by[n], cur_by[n], calibration)
               for n in shared]
    return RegressionReport(
        base, cur, queries, calibration,
        only_in_base=sorted(n for n in base_by if n not in cur_by),
        only_in_cur=sorted(n for n in cur_by if n not in base_by))


def diff_latest(trajectory: List[dict]) -> Optional[RegressionReport]:
    """Diff the last two entries of one suite's trajectory, or None."""
    if len(trajectory) < 2:
        return None
    return diff_entries(trajectory[-2], trajectory[-1])
