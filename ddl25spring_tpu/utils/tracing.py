"""Tracing compatibility shims + CSV result persistence.

The tracing/profiling half of this module moved to
``ddl25spring_tpu/telemetry/trace.py`` (the ISSUE-8 span layer): ``Spans``,
``StepTimer`` and ``device_trace`` are re-exported here unchanged so
existing imports keep working, but there is now ONE tracing path — the
span Tracer feeds the same ``Spans`` accumulators the registry absorbs,
and every ``with`` span of either also enters a
``jax.profiler.TraceAnnotation``, whoever started the profiler
(``device_trace`` is the plain start and stop of it). New code should
import from ``telemetry.trace`` directly.

What still lives here is result persistence:

- ``atomic_write_csv``: temp-file + ``os.replace`` CSV rewrite.
- ``ResultSink``: append experiment records (RunResult or dicts) to CSV.
"""

from __future__ import annotations

import contextlib
import csv
import os
import threading
from typing import Any, Dict, List, Optional

from ..telemetry.trace import Spans, StepTimer, device_trace  # noqa: F401

__all__ = ["Spans", "StepTimer", "device_trace", "atomic_write_csv",
           "ResultSink"]


def atomic_write_csv(path: str, fieldnames: List[str],
                     rows: List[Dict[str, Any]]) -> None:
    """Rewrite a CSV atomically: temp file in the same directory +
    ``os.replace``, preserving the original's mode, with the temp file
    unlinked on failure. The one implementation of this dance — used by
    ResultSink's header widening and experiments.common.dedupe_csv, both of
    which run in environments where processes get killed mid-write."""
    import tempfile

    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               suffix=".csv.tmp")
    try:
        with os.fdopen(fd, "w", newline="") as f:
            writer = csv.DictWriter(f, fieldnames=fieldnames, restval="")
            writer.writeheader()
            writer.writerows(rows)
        if os.path.exists(path):
            os.chmod(tmp, os.stat(path).st_mode & 0o7777)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


class ResultSink:
    """Append-only CSV sink for experiment records.

    Accepts dicts or RunResult-like objects (anything with ``as_df``); the
    CSV header is taken from the first record (reference idiom: results
    persisted to CSV for re-plotting, hw03 cells 11, 18, 29).

    Thread-safe within one process: concurrent ``write`` calls (training
    thread + watchdog/monitor thread) serialize on a lock, so a
    header-widening rewrite can never interleave with another append and
    drop rows (pinned in tests/test_telemetry.py).
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        self._fieldnames: Optional[List[str]] = None
        if os.path.exists(path):
            with open(path, newline="") as f:
                reader = csv.reader(f)
                self._fieldnames = next(reader, None)

    def write(self, record: Any) -> None:
        if hasattr(record, "as_df"):
            for row in record.as_df().to_dict(orient="records"):
                self._locked_write_row(row)
        else:
            self._locked_write_row(dict(record))

    def _locked_write_row(self, row: Dict[str, Any]) -> None:
        with self._lock:
            self._write_row(row)

    def _write_row(self, row: Dict[str, Any]) -> None:
        new_file = self._fieldnames is None
        if new_file:
            self._fieldnames = list(row.keys())
        extra = [k for k in row if k not in self._fieldnames]
        if extra:
            # Widen: rewrite the file under the union header instead of
            # silently dropping the new fields. Pure-csv round-trip (no type
            # inference mangling existing values), atomic so a crash
            # mid-widen cannot lose prior records.
            self._fieldnames = self._fieldnames + extra
            if os.path.exists(self.path):
                with open(self.path, newline="") as f:
                    old_rows = list(csv.DictReader(f))
                atomic_write_csv(self.path, self._fieldnames, old_rows)
        with open(self.path, "a", newline="") as f:
            writer = csv.DictWriter(f, fieldnames=self._fieldnames,
                                    restval="")
            if new_file:
                writer.writeheader()
            writer.writerow(row)

    def read_df(self):
        import pandas as pd
        return pd.read_csv(self.path)
