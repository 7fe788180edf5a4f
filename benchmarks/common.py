"""Shared benchmark plumbing."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")


def out_path(name: str) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    return os.path.join(ARTIFACTS, name)


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0


def emit(rows, csv_name=None, echo=True):
    """rows: list[dict] -> CSV file + stdout."""
    import csv as _csv

    if not rows:
        return
    fields = list(rows[0].keys())
    lines = [",".join(fields)]
    for r in rows:
        lines.append(",".join(str(r.get(f, "")) for f in fields))
    text = "\n".join(lines)
    if echo:
        print(text)
    if csv_name:
        with open(out_path(csv_name), "w") as fh:
            fh.write(text + "\n")
