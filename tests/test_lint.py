"""Tier-1 lint guard: flake8 over vitax/ tests/ tools/ chip_smoke.py with the
repo's .flake8 settings (max-line-length 120), plus firing/silent fixtures
for VTX109 (network calls without an explicit timeout=). Skips the flake8
arm cleanly when flake8 is not installed (the CI images don't ship
it); tools/lint.sh is the equivalent shell entry point.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from vitax.analysis.ast_lint import lint_source

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_flake8_clean():
    pytest.importorskip("flake8")
    r = subprocess.run(
        [sys.executable, "-m", "flake8", "vitax/", "tests/", "tools/",
         "chip_smoke.py"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"flake8 findings:\n{r.stdout}\n{r.stderr}"


def test_max_line_length_120():
    """flake8's E501 at 120, enforced without flake8 present: the one lint
    rule cheap enough to check directly, so the guard still bites on images
    where test_flake8_clean skips."""
    bad = []
    targets = [os.path.join(REPO, "chip_smoke.py")]
    for sub in ("vitax", "tests", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, sub)):
            targets += [os.path.join(dirpath, f) for f in files
                        if f.endswith(".py")]
    for path in targets:
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f, 1):
                if len(line.rstrip("\n")) > 120:
                    bad.append(f"{os.path.relpath(path, REPO)}:{i} "
                               f"({len(line.rstrip())} chars)")
    assert not bad, "lines over 120 chars:\n" + "\n".join(bad)


def _codes(source: str):
    return [(f.code, f.severity)
            for f in lint_source(textwrap.dedent(source), "fixture.py")]


def test_vtx109_fires_on_network_calls_without_timeout():
    src = """
    import socket
    import urllib.request

    def probe(url, addr):
        urllib.request.urlopen(url)
        socket.create_connection(addr)
    """
    assert _codes(src) == [("VTX109", "ERROR"), ("VTX109", "ERROR")]


def test_vtx109_silent_with_explicit_timeout():
    src = """
    import socket
    import urllib.request
    from http.client import HTTPConnection

    def probe(url, addr, host):
        urllib.request.urlopen(url, timeout=5.0)
        urllib.request.urlopen(url, None, 5.0)   # positional timeout
        socket.create_connection(addr, 2.0)
        HTTPConnection(host, 80, timeout=1.0)
    """
    assert _codes(src) == []


def test_vtx109_suppression_comment():
    src = """
    import urllib.request

    def probe(url):
        urllib.request.urlopen(url)  # vtx: ignore[VTX109] caller owns deadline
    """
    assert _codes(src) == []


def test_vtx109_production_tree_clean():
    """Every urlopen/create_connection/HTTPConnection in vitax/ and tools/
    carries an explicit timeout (or a reasoned suppression)."""
    findings = []
    for sub in ("vitax", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, sub)):
            for f in files:
                if not f.endswith(".py"):
                    continue
                path = os.path.join(dirpath, f)
                with open(path, encoding="utf-8") as fh:
                    findings += [x for x in lint_source(fh.read(), path)
                                 if x.code == "VTX109"]
    assert not findings, "\n".join(str(f) for f in findings)
