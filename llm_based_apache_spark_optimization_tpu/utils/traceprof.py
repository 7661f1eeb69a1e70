"""Device-time profiling from jax.profiler traces — no TensorBoard needed.

Wall-clock around a jitted call includes a host↔device dispatch+sync
floor, which silently dominates short programs and understates
MFU/bandwidth. The profiler's trace.json.gz records actual device op
timelines; `tensorboard_plugin_profile`'s converter is broken in
this image, so this module parses the Chrome-trace JSON directly:

    with device_trace() as tr:
        fn(args)          # any number of dispatches
    tr.device_time_s()    # summed device-op wall, overlaps merged
    tr.top_ops(10)        # [(name, seconds, count)] hottest first

Works on CPU and TPU backends (tests run it on CPU). Event model: each
trace "X" (complete) event on a device-lane thread contributes its `dur`;
lanes are identified by their process name containing the device prefix
(e.g. "/device:TPU:0" / "TFRT-CPU"). Device time is reported two ways:
summed op time (`op_time_s`, counts parallel lanes twice) and merged
busy time (`device_time_s`, union of intervals — the honest denominator
for MFU on one chip).
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import tempfile
import threading
from typing import Dict, List, Optional, Tuple


class Trace:
    def __init__(self):
        self.ops: Dict[str, List[float]] = {}
        self.intervals: List[Tuple[float, float]] = []

    # ------------------------------------------------------------- loading

    def load_dir(self, trace_dir: str) -> "Trace":
        for path in glob.glob(
            os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
        ):
            with gzip.open(path, "rt") as f:
                self._ingest(json.load(f))
        return self

    def _ingest(self, doc: dict) -> None:
        events = doc.get("traceEvents", [])
        # Lane = (pid, tid). Host threads share the device PID (on the CPU
        # backend the 'python' dispatch thread lives under '/host:CPU'
        # beside the real 'tf_XLAPjRtCpuClient/*' compute lane), so the
        # filter must be by THREAD name, not process name. Known op lanes:
        # TPU traces put per-op events on threads named 'XLA Ops' (the
        # 'XLA Modules' / 'Steps' lanes are whole-program spans that would
        # double-count); PjRt CPU puts them on 'tf_XLAPjRtCpuClient/...'.
        tid_name = {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "thread_name":
                tid_name[(e.get("pid"), e.get("tid"))] = (
                    e.get("args", {}).get("name", "")
                )
        op_lanes = {
            lane for lane, name in tid_name.items()
            if "XLA Ops" in name or name.startswith("tf_")
        }
        if not op_lanes:
            # Unknown backend naming: fall back to everything except
            # obvious host / aggregate lanes.
            deny = ("python", "main", "profiler", "XLA Modules", "Steps",
                    "TraceMe", "Framework")
            op_lanes = {
                lane for lane, name in tid_name.items()
                if not any(d.lower() in name.lower() for d in deny)
            }
        for e in events:
            if (e.get("ph") != "X"
                    or (e.get("pid"), e.get("tid")) not in op_lanes):
                continue
            dur = float(e.get("dur", 0.0)) * 1e-6  # us -> s
            if dur <= 0.0:
                continue
            name = e.get("name", "?")
            self.ops.setdefault(name, []).append(dur)
            ts = float(e.get("ts", 0.0)) * 1e-6
            self.intervals.append((ts, ts + dur))

    # ------------------------------------------------------------ queries

    def op_time_s(self) -> float:
        """Summed op durations (parallel lanes double-count)."""
        return sum(sum(v) for v in self.ops.values())

    def device_time_s(self) -> float:
        """Union of op intervals — device busy wall-clock."""
        if not self.intervals:
            return 0.0
        merged = 0.0
        cur_a, cur_b = None, None
        for a, b in sorted(self.intervals):
            if cur_b is None or a > cur_b:
                if cur_b is not None:
                    merged += cur_b - cur_a
                cur_a, cur_b = a, b
            else:
                cur_b = max(cur_b, b)
        merged += cur_b - cur_a
        return merged

    def top_ops(self, n: int = 10) -> List[Tuple[str, float, int]]:
        rows = [
            (name, sum(durs), len(durs)) for name, durs in self.ops.items()
        ]
        rows.sort(key=lambda r: -r[1])
        return rows[:n]


# ---------------------------------------------------------------------------
# On-demand device profiling (/debug/profile): the fleet-wide single-capture
# guard + app-configured defaults. jax.profiler supports ONE trace at a time
# per process, and a capture is expensive enough that two overlapping ones
# would corrupt each other's artifacts — so schedulers (every replica, every
# model) funnel through this process-wide guard: at most one capture in
# flight, whoever holds it releases on finish/abort.

_capture_lock = threading.Lock()
_capture_owner: Optional[str] = None

#: App-startup overrides (AppConfig.profile_dir / profile_rounds via
#: `reconfigure_profile`); env fallbacks LSOT_PROFILE_DIR /
#: LSOT_PROFILE_ROUNDS keep the knobs usable without the app wiring.
_profile_dir_override: Optional[str] = None
_profile_rounds_override: Optional[int] = None


def reconfigure_profile(profile_dir: Optional[str] = None,
                        rounds: Optional[int] = None) -> None:
    """App-startup wiring seam (AppConfig.profile_dir/profile_rounds) —
    same pattern as `tracing.TRACER.reconfigure`, so the AppConfig knobs
    are honored, not silent no-ops."""
    global _profile_dir_override, _profile_rounds_override
    _profile_dir_override = profile_dir or None
    _profile_rounds_override = int(rounds) if rounds else None


def profile_defaults() -> Tuple[Optional[str], int]:
    """(artifact base dir or None, default rounds) for an on-demand
    capture. Dir precedence: reconfigure_profile > LSOT_PROFILE_DIR >
    the tracer's export dir (the capture lands NEXT TO the existing
    per-request trace exports) > None (caller tempdirs)."""
    d = _profile_dir_override or os.environ.get("LSOT_PROFILE_DIR") or None
    if not d:
        from .tracing import TRACER

        d = TRACER.export_dir or None
    if _profile_rounds_override:
        return d, _profile_rounds_override
    try:
        n = int(os.environ.get("LSOT_PROFILE_ROUNDS", "8"))
    except ValueError:
        n = 8
    return d, max(1, n)


def try_acquire_capture(owner: str) -> bool:
    """Claim the process-wide capture slot; False when someone holds it
    (the /debug/profile 409)."""
    global _capture_owner
    with _capture_lock:
        if _capture_owner is not None:
            return False
        _capture_owner = owner
        return True


def release_capture(owner: str) -> None:
    """Release the slot (idempotent; only the owner's release counts, so
    a late abort cannot free a successor's capture)."""
    global _capture_owner
    with _capture_lock:
        if _capture_owner == owner:
            _capture_owner = None


def capture_owner() -> Optional[str]:
    with _capture_lock:
        return _capture_owner


def profile_options():
    """`jax.profiler.ProfileOptions` of an on-demand capture: the Python
    tracer off. It hooks every call of the serving loop's thread, which
    slows the loop it is meant to observe and fills the artifact with its
    frames; the host's account in the trace is the `sched.*` / `stream.*`
    / `http.*` spans (`utils/observability.StageTimer`)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def find_profile_artifacts(trace_dir: str) -> List[str]:
    """What a jax.profiler capture wrote under `trace_dir`: the
    Perfetto-loadable `*.trace.json.gz` (`Trace.load_dir` parses them,
    scripts/obs_smoke.sh asserts them non-empty) and the `*.xplane.pb`
    they were made from, which `jax.profiler.ProfileData` and the
    benchmark read."""
    return sorted(
        path for pattern in ("*.trace.json.gz", "*.xplane.pb")
        for path in glob.glob(os.path.join(trace_dir, "**", pattern),
                              recursive=True)
    )


@contextlib.contextmanager
def device_trace(trace_dir: str | None = None):
    """Profile the enclosed region; yields a Trace filled on exit."""
    import jax

    tr = Trace()
    own = trace_dir is None
    d = trace_dir or tempfile.mkdtemp(prefix="lsot_trace_")
    try:
        with jax.profiler.trace(d):
            yield tr
        tr.load_dir(d)
    finally:
        if own:
            import shutil

            shutil.rmtree(d, ignore_errors=True)
