"""Where a run is, on standard error: a run that is cut short still
says how far it got and what each stage of its set-up cost."""

from __future__ import annotations

import sys
import time


def progress(t_process: float, msg: str) -> None:
    print(f"[bench {time.perf_counter() - t_process:7.1f}s] {msg}",
          file=sys.stderr, flush=True)
