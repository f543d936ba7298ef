"""Fixtures of the benchmark's CPU tests: the benchmark on the import path,
and a run driven on the CPU at tiny sizes."""

from __future__ import annotations

import io
import json
from pathlib import Path

import pytest

from bench_tiny import TINY, merge


@pytest.fixture()
def tiny(monkeypatch):
    """The harness with tiny configurations and traffic, and the CPU in
    place of the chip. Returns ``run(cell, seed, seconds, trace) -> dict``
    (the parsed result line)."""
    from bench import harness, work
    real = harness.load_json

    def load_json(kind, name):
        d = real(kind, name)
        return merge(d, TINY.get((kind, name), {}))

    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(harness, "CACHE_DIR", Path("/nonexistent"))
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)

    def cpu(chips):
        import jax
        return jax.devices(), work.Peaks("cpu", 197e12, 819e9)

    def run(cell, seed=7, seconds=0.5, trace=False):
        out, err = io.StringIO(), io.StringIO()
        rc = harness.run(cell, seed, seconds, trace, 0.0, require_chip=cpu,
                         out=out, err=err)
        assert rc == 0
        return json.loads(out.getvalue().strip().splitlines()[-1])

    return run
