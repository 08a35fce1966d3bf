"""Overlap verification for the pipelined bucket schedule (comm/bucket.py
Pipelined), from the compiled SPMD HLO on 8 forced host devices.

What "overlap" means at the HLO level: inside the pipeline's scan body,
the grouped all-reduce for stage *i-1* must consume ONLY the loop carry —
never this iteration's compress output — so a backend with async
collectives can hoist the compress between ``all-reduce-start`` and
``all-reduce-done``.  The CPU backend keeps collectives synchronous (no
start/done pair to span), so the test asserts the *schedulability*
precondition directly on the dependence graph, plus the program-size
claim: collective op count O(1) in the bucket count vs the serial path's
2 per bucket.  When the backend does split collectives (TPU/GPU), the
start/done spanning check kicks in automatically.

Device count must be forced before jax initializes, so the compile runs
in a subprocess.
"""
import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import json, sys
# the SAME builder benchmarks/bench_bucketing.py measures — the
# overlap-verified program and the benchmarked program cannot drift
from repro.testing import AB_SMALL_CAP, build_ab_reduction

out = {}
for name in ("serial", "pipelined"):
    b = build_ab_reduction(name, AB_SMALL_CAP)
    txt = b["fn"].lower(b["params"], b["state"]).compile().as_text()
    open(os.path.join(sys.argv[1], name + ".hlo"), "w").write(txt)
    out[name + "_buckets"] = b["n_buckets"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def hlo_pair(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("hlo"))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _CHILD, d], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    import json
    meta = json.loads(r.stdout.strip().splitlines()[-1])
    with open(os.path.join(d, "serial.hlo")) as f:
        serial = f.read()
    with open(os.path.join(d, "pipelined.hlo")) as f:
        pipelined = f.read()
    return serial, pipelined, meta


from repro.testing import count_allreduce_ops as _collective_ops  # noqa: E402
from repro.testing import count_allreduce_operands  # noqa: E402


def _computations(txt):
    """name -> list of op lines, for every computation in the module."""
    comps, cur, lines = {}, None, []
    for line in txt.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->.*{", line)
        if m:
            cur, lines = m.group(1), []
            comps[cur] = lines
        elif cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                lines.append(line)
    return comps


def _defs_and_deps(lines):
    """op name -> set of operand op names (same-computation only)."""
    defs = {}
    for ln in lines:
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", ln)
        if m:
            defs[m.group(1)] = ln
    deps = {}
    for name, ln in defs.items():
        body = ln.split("=", 1)[1]
        deps[name] = {t for t in re.findall(r"%([\w.\-]+)", body)
                      if t in defs and t != name}
    return defs, deps


def _closure(start, deps):
    seen, todo = set(), list(start)
    while todo:
        n = todo.pop()
        if n in seen:
            continue
        seen.add(n)
        todo.extend(deps.get(n, ()))
    return seen


def test_pipelined_program_size_is_o1_in_buckets(hlo_pair):
    """Serial unrolls one all-reduce pair per bucket; the pipeline's scan
    keeps the collective op count constant.  XLA's all-reduce combiner
    may merge the serial program's independent all-reduces into a few
    tuple ops, so the serial side counts the arrays reduced."""
    serial, pipelined, meta = hlo_pair
    n = meta["serial_buckets"]
    assert n >= 8                    # the A/B really is multi-bucket
    assert count_allreduce_operands(serial) == 2 * n
    assert _collective_ops(pipelined) <= 6


def test_pipelined_collective_overlaps_next_compress(hlo_pair):
    """Inside the scan body, the all-reduce depends only on the loop
    carry — not on the TopK/sort compress ops issued in the same
    iteration — so an async backend can run the compress inside the
    collective's start/done window.  On backends that split collectives,
    additionally require the start/done pair to span the compress."""
    _, pipelined, _ = hlo_pair
    comps = _computations(pipelined)
    body = None
    for name, lines in comps.items():
        blob = "\n".join(lines)
        has_ar = "all-reduce(" in blob or "all-reduce-start(" in blob
        has_compress = "custom-call" in blob or "sort(" in blob
        if has_ar and has_compress:
            body = lines
            break
    assert body is not None, \
        "no computation holds both the collective and the compress — " \
        "the pipeline's scan body should contain both"
    defs, deps = _defs_and_deps(body)
    ar_ops = [n for n, ln in defs.items()
              if "all-reduce(" in ln or "all-reduce-start(" in ln]
    compress_ops = {n for n, ln in defs.items()
                    if "custom-call" in ln or re.search(r"\bsort\(", ln)}
    assert ar_ops and compress_ops
    reached = _closure([t for n in ar_ops for t in deps[n]], deps)
    overlap_blockers = reached & compress_ops
    assert not overlap_blockers, \
        f"the scan body's all-reduce depends on this iteration's " \
        f"compress ({sorted(overlap_blockers)[:4]}...) — the collective " \
        f"must consume only the loop carry"
    # async backends: the done must come after the compress in schedule
    # order, i.e. the start/done pair spans it
    blob = "\n".join(body)
    if "all-reduce-start(" in blob:
        start = blob.index("all-reduce-start(")
        done = blob.index("all-reduce-done(")
        compress_at = blob.index("custom-call")
        assert start < compress_at < done
