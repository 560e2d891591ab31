"""Structured per-process metrics & logging.

Reference parity (SURVEY.md §5): the reference's observability was ``print``
per rank, interleaved in the mpirun console. Here every record is one JSON
line tagged with the process index and wall-clock time, so multi-host runs
produce machine-mergeable streams (the benchmark harness consumes these), and
the console mirror keeps the reference's at-a-glance ergonomics.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Optional, TextIO

import jax


def _to_jsonable(v: Any) -> Any:
    # exact python types pass through untouched (a bool/str must not be
    # float()-coerced: float(True) and float("007") both "work")
    if isinstance(v, (str, bool, int, float, type(None), list, dict)):
        return v
    if hasattr(v, "tolist"):  # np/jax scalars and arrays, any rank
        return v.tolist()
    try:
        return float(v)  # other numeric scalar types
    except (TypeError, ValueError):
        return repr(v)


class MetricsLogger:
    """JSONL metrics stream (+ optional console mirror).

    Args:
      path: JSONL file to append to; parent dirs are created. When None,
        records go only to the console mirror.
      tag: short run identifier stamped on every record (e.g. "easgd").
      echo: also print a compact human-readable line to stderr.
      all_processes: by default only process 0 writes (replicated metrics are
        identical across processes); set True for genuinely per-process
        streams — each process should then use its own ``path``.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        tag: str = "train",
        echo: bool = True,
        all_processes: bool = False,
        _stream: Optional[TextIO] = None,
    ):
        self.tag = tag
        self.echo = echo
        self.process = jax.process_index()
        self._active = all_processes or self.process == 0
        self._f: Optional[TextIO] = _stream
        if path is not None and self._active and _stream is None:
            parent = os.path.dirname(os.path.abspath(path))
            os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a")

    def log(self, step: int, **metrics: Any) -> None:
        if not self._active:
            return
        rec = {
            "ts": round(time.time(), 3),
            "tag": self.tag,
            "process": self.process,
            "step": int(step),
            **{k: _to_jsonable(v) for k, v in metrics.items()},
        }
        if self._f is not None:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()
        if self.echo:
            body = " ".join(
                f"{k}={v:.5g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in rec.items()
                if k not in ("ts", "tag", "process")
            )
            print(f"[{self.tag}] {body}", file=sys.stderr)

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
