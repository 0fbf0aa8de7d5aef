"""Tier-1 runs the benchmark harness's own tests (``perfbench/tests``).

The harness keeps its own ``conftest.py`` and ``sys.path`` (its tests import
``perfbench`` from the repo root and a second kind from a directory of their
own), so they run in ONE subprocess of their own, once a session, and every
case there is a case here: a test that asserts its own node passed. Nothing
under ``perfbench/`` is edited for this; a case added there is collected
here at the next run.
"""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUITE = "perfbench/tests"


def _pytest(*args, timeout):
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith(("PYTEST_", "PY_COLORS"))
    }
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run(
        [sys.executable, "-m", "pytest", SUITE, "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout,
    )


def _node_ids():
    out = _pytest("--collect-only", timeout=300)
    ids = [l for l in out.stdout.splitlines() if l.startswith(SUITE + "/")]
    assert ids, f"no harness tests collected:\n{out.stdout}\n{out.stderr}"
    return ids


NODE_IDS = _node_ids()


@pytest.fixture(scope="module")
def outcomes(tmp_path_factory):
    """One run of the whole harness suite: node id -> None when it passed,
    else (failure | error | skipped, what its junit case says)."""
    xml = tmp_path_factory.mktemp("perfbench") / "junit.xml"
    run = _pytest(f"--junitxml={xml}", "-o", "junit_family=xunit1", timeout=900)
    results = {}
    for case in ET.parse(xml).getroot().iter("testcase"):
        node = "::".join(
            [case.get("file"), *case.get("classname").split(".")[
                len(case.get("file")[:-3].split("/")):
            ], case.get("name")]
        )
        bad = [c for c in case if c.tag in ("failure", "error", "skipped")]
        results[node] = (
            (bad[0].tag, f"{bad[0].get('message')}\n{bad[0].text}")
            if bad else None
        )
    return results, run


@pytest.mark.parametrize("node", NODE_IDS)
def test_harness_case(node, outcomes):
    results, run = outcomes
    assert node in results, (
        f"{node} did not run (exit {run.returncode}):\n"
        f"{run.stdout[-2000:]}\n{run.stderr[-2000:]}"
    )
    if results[node] is not None:
        kind, said = results[node]
        if kind == "skipped":
            pytest.skip(said)
        pytest.fail(f"{kind}: {said}", pytrace=False)
