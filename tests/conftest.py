"""Test config.  The main pytest process keeps ONE CPU device — multi-device
checks run in subprocesses (tests/dist_checks.py), and the 512-device env is
reserved for the dry-run (launch/dryrun.py)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_dist_group(group: str, timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "dist_checks.py"),
         group],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    if r.returncode != 0:
        raise AssertionError(
            f"dist_checks {group} failed:\n{r.stdout}\n{r.stderr[-4000:]}")


def host_events(trace_dir):
    """(name, start_ns, end_ns, line) of every event on the host planes of
    the one .xplane.pb under `trace_dir`, by start."""
    import glob

    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for pl in jax.profiler.ProfileData.from_file(path).planes:
        if pl.name.startswith("/host"):
            for i, ln in enumerate(pl.lines):
                out += [(e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns), i)
                        for e in ln.events]
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="session")
def repo_root():
    return REPO
