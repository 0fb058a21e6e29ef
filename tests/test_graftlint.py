"""graftlint (tools/graftlint): the invariants-as-code lint pass — tier-1.

Three layers, mirroring the tool's own structure:

1. **fixture tests** — per checker, at least one true-positive snippet (the
   violation is found) and one false-positive regression snippet (the
   sanctioned look-alike is NOT found), built as tiny synthetic repos in
   tmp_path so each rule's boundary is pinned independently of this repo's
   code;
2. **machinery tests** — pragmas, baseline matching/staleness, import-graph
   semantics (lazy vs top-level edges, parent-package edges);
3. **the meta-test** — the full pass over THIS repo must report zero
   non-baselined findings, and the CLI must exit 0 (and nonzero once a
   violation is introduced). This is the test that turns the house rules into
   a commit gate.

graftlint is stdlib-only and never imports repo code, so these tests run
without touching a jax backend (the fixture repos reference jax only as text).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:            # tools.* is a namespace package off the root
    sys.path.insert(0, REPO)

from tools.graftlint import (  # noqa: E402
    build_graph,
    load_baseline,
    run_lint,
)
from tools.graftlint.baseline import Baseline, default_baseline_path  # noqa: E402
from tools.graftlint.core import parse_pragmas  # noqa: E402

PKG = "csed_514_project_distributed_training_using_pytorch_tpu"

# The fixture package deliberately reuses this repo's rule paths (rules.py is
# package-relative), so e.g. fakepkg/serving/router.py is declared
# backend-free and fakepkg/train/lm.py must gate its writes.
BASE_FILES = {
    "fakepkg/__init__.py": "",
    "fakepkg/utils/__init__.py": "",
    "fakepkg/utils/telemetry_events.py":
        'EVENT_KINDS = {"known": "a registered kind"}\n',
    "fakepkg/serving/__init__.py": "",
    "fakepkg/train/__init__.py": "",
    "fakepkg/resilience/__init__.py": "",
}


def lint(tmp_path, files, checks=None):
    """Write ``files`` over the fixture skeleton and lint the tmp repo."""
    for rel, src in {**BASE_FILES, **files}.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    findings, _graph = run_lint(str(tmp_path), checks=checks)
    return findings


def by_check(findings, name):
    return [f for f in findings if f.check == name]


# -----------------------------------------------------------------------------------
# backend-purity
# -----------------------------------------------------------------------------------


def test_backend_purity_transitive_true_positive(tmp_path):
    fs = {
        "fakepkg/helper.py": "import jax\n",
        "fakepkg/serving/router.py": "from fakepkg import helper\n",
    }
    found = by_check(lint(tmp_path, fs, ["backend-purity"]), "backend-purity")
    assert len(found) == 1
    f = found[0]
    assert f.path == "fakepkg/serving/router.py"
    assert f.line == 1                      # the import line starting the chain
    assert "fakepkg.helper" in f.message and "jax" in f.message


def test_backend_purity_parent_package_edge(tmp_path):
    # launch.py itself is clean; the PARENT __init__ imports jax eagerly —
    # the exact leak class fixed in train/__init__.py when this tool landed.
    fs = {
        "fakepkg/train/__init__.py": "from fakepkg.train import step\n",
        "fakepkg/train/step.py": "import jax\n",
        "fakepkg/train/launch.py": "import os\n",
        "fakepkg/serving/router.py": "from fakepkg.train.launch import os\n",
    }
    found = by_check(lint(tmp_path, fs, ["backend-purity"]), "backend-purity")
    # Both declared modules report it: the router that imports the launcher, and
    # (since PR 21 declared it backend-free too) the launcher itself.
    assert sorted(f.path for f in found) == ["fakepkg/serving/router.py",
                                             "fakepkg/train/launch.py"]
    assert all("fakepkg.train" in f.message for f in found)


def test_backend_purity_lazy_import_is_sanctioned(tmp_path):
    fs = {
        "fakepkg/serving/router.py": (
            "import os\n"
            "def resume():\n"
            "    import jax\n"
            "    return jax\n"),
    }
    assert lint(tmp_path, fs, ["backend-purity"]) == []


def test_backend_purity_pragma_excludes_edge(tmp_path):
    fs = {
        "fakepkg/serving/router.py":
            "import jax  # graftlint: disable=backend-purity\n",
    }
    assert lint(tmp_path, fs, ["backend-purity"]) == []


def test_backend_purity_out_of_scope_module_free(tmp_path):
    fs = {"fakepkg/models.py": "import jax\n"}        # not declared backend-free
    assert lint(tmp_path, fs, ["backend-purity"]) == []


# -----------------------------------------------------------------------------------
# resolve-guard
# -----------------------------------------------------------------------------------


def test_resolve_guard_true_positive(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            "def done(fut, value):\n"
            "    fut.set_result(value)\n"),
    }
    found = by_check(lint(tmp_path, fs, ["resolve-guard"]), "resolve-guard")
    assert len(found) == 1 and found[0].line == 2
    assert "set_result" in found[0].message


def test_resolve_guard_guarded_is_clean(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            "import concurrent.futures\n"
            "def done(fut, value, err):\n"
            "    try:\n"
            "        if err is not None:\n"
            "            fut.set_exception(err)\n"
            "        else:\n"
            "            fut.set_result(value)\n"
            "    except concurrent.futures.InvalidStateError:\n"
            "        pass\n"),
    }
    assert lint(tmp_path, fs, ["resolve-guard"]) == []


def test_resolve_guard_else_leg_not_guarded(tmp_path):
    # try/else runs OUTSIDE the guarded region — a resolve there can still
    # lose the race and kill the thread.
    fs = {
        "fakepkg/serving/server.py": (
            "def done(fut, value):\n"
            "    try:\n"
            "        x = 1\n"
            "    except InvalidStateError:\n"
            "        pass\n"
            "    else:\n"
            "        fut.set_result(value)\n"),
    }
    assert len(by_check(lint(tmp_path, fs, ["resolve-guard"]),
                        "resolve-guard")) == 1


def test_resolve_guard_wide_handler_and_tuple(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            "def done(fut, v):\n"
            "    try:\n"
            "        fut.set_result(v)\n"
            "    except (ValueError, InvalidStateError):\n"
            "        pass\n"
            "def done2(fut, v):\n"
            "    try:\n"
            "        fut.set_result(v)\n"
            "    except Exception:\n"
            "        pass\n"),
    }
    assert lint(tmp_path, fs, ["resolve-guard"]) == []


# -----------------------------------------------------------------------------------
# telemetry-schema
# -----------------------------------------------------------------------------------


def test_telemetry_schema_unregistered_kind(tmp_path):
    fs = {
        "fakepkg/serving/server.py":
            'def emit(w):\n    w.emit({"event": "mystery", "x": 1})\n',
    }
    found = by_check(lint(tmp_path, fs, ["telemetry-schema"]),
                     "telemetry-schema")
    assert len(found) == 1
    assert "'mystery'" in found[0].message


def test_telemetry_schema_registered_and_dynamic_kinds_clean(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            'def emit(w, kind):\n'
            '    w.emit({"event": "known"})\n'
            '    w.emit({"event": kind})\n'      # dynamic: reader passthrough
            '    d = {"event": "known"}\n'),
    }
    assert lint(tmp_path, fs, ["telemetry-schema"]) == []


def test_telemetry_schema_setdefault_form(tmp_path):
    fs = {
        "fakepkg/serving/server.py":
            'def emit(p):\n    p.setdefault("event", "drifted")\n',
    }
    assert len(by_check(lint(tmp_path, fs, ["telemetry-schema"]),
                        "telemetry-schema")) == 1


def test_telemetry_schema_missing_registry_is_loud(tmp_path):
    files = {k: v for k, v in BASE_FILES.items()
             if k != "fakepkg/utils/telemetry_events.py"}
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
    findings, _ = run_lint(str(tmp_path), checks=["telemetry-schema"])
    assert len(findings) == 1
    assert "cannot read" in findings[0].message


def test_telemetry_schema_computed_registry_is_loud(tmp_path):
    fs = {"fakepkg/utils/telemetry_events.py":
          "EVENT_KINDS = dict(known='x')\n"}       # not a pure dict literal
    findings = lint(tmp_path, fs, ["telemetry-schema"])
    assert len(findings) == 1
    assert "pure dict literal" in findings[0].message


# -----------------------------------------------------------------------------------
# process0-gate
# -----------------------------------------------------------------------------------


def test_process0_gate_raw_write_true_positive(tmp_path):
    fs = {
        "fakepkg/train/lm.py": (
            "import json\n"
            "def run(path, history):\n"
            "    with open(path, 'w') as f:\n"
            "        json.dump(history, f)\n"),
    }
    found = by_check(lint(tmp_path, fs, ["process0-gate"]), "process0-gate")
    assert len(found) == 2                 # open('w') AND json.dump
    assert all("process-0 gate" in f.message for f in found)


def test_process0_gate_gated_write_is_clean(tmp_path):
    fs = {
        "fakepkg/train/lm.py": (
            "import json\n"
            "from fakepkg.utils import metrics as M\n"
            "def run(path, history, pidx):\n"
            "    if M.is_logging_process():\n"
            "        with open(path, 'w') as f:\n"
            "            json.dump(history, f)\n"
            "    if pidx.process_index() == 0:\n"
            "        open(path, 'a').close()\n"),
        "fakepkg/utils/metrics.py": "def is_logging_process():\n    return True\n",
    }
    assert lint(tmp_path, fs, ["process0-gate"]) == []


def test_process0_gate_reads_and_out_of_scope_clean(tmp_path):
    fs = {
        "fakepkg/train/lm.py": (
            "def run(path):\n"
            "    return open(path).read()\n"),    # read mode: no gate needed
        "fakepkg/serving/engine2.py": (
            "def run(path):\n"
            "    open(path, 'w').close()\n"),     # not an SPMD trainer module
    }
    assert lint(tmp_path, fs, ["process0-gate"]) == []


# -----------------------------------------------------------------------------------
# host-sync-hazard
# -----------------------------------------------------------------------------------


def test_host_sync_hot_method_true_positive(tmp_path):
    fs = {
        "fakepkg/serving/engine.py": (
            "class Engine:\n"
            "    def step(self):\n"
            "        cache, tok = self._step_jit(1)\n"
            "        return float(tok)\n"),
    }
    found = by_check(lint(tmp_path, fs, ["host-sync-hazard"]),
                     "host-sync-hazard")
    assert len(found) == 1 and found[0].line == 4
    assert "float" in found[0].message


def test_host_sync_reassignment_clears_taint(tmp_path):
    # The one sanctioned shape: a single batched np.asarray fetch (flagged —
    # in production it carries the pragma), after which the host copy is free.
    fs = {
        "fakepkg/serving/engine.py": (
            "import numpy as np\n"
            "class Engine:\n"
            "    def step(self):\n"
            "        cache, tok = self._step_jit(1)\n"
            "        tok = np.asarray(tok)\n"
            "        return int(tok[0])\n"),      # host data now: NOT flagged
    }
    found = by_check(lint(tmp_path, fs, ["host-sync-hazard"]),
                     "host-sync-hazard")
    assert len(found) == 1 and found[0].line == 5


def test_host_sync_host_values_and_cold_methods_clean(tmp_path):
    fs = {
        "fakepkg/serving/engine.py": (
            "import numpy as np\n"
            "class Engine:\n"
            "    def step(self):\n"
            "        n = int(self._prompt_len[0])\n"      # host array attr
            "        a = np.asarray([1, 2])\n"            # host literal
            "        return n + a[0]\n"
            "    def report(self):\n"                     # not a hot region
            "        _, tok = self._step_jit(1)\n"
            "        return float(tok)\n"),
    }
    assert lint(tmp_path, fs, ["host-sync-hazard"]) == []


def test_host_sync_scan_body_params_are_traced(tmp_path):
    fs = {
        "fakepkg/train/step.py": (
            "from jax import lax\n"
            "def make_epoch(xs):\n"
            "    def body(carry, x):\n"
            "        bad = float(x)\n"                    # sync on a tracer
            "        return carry, bad\n"
            "    return lax.scan(body, 0.0, xs)\n"
            "def host_helper(x):\n"
            "    return float(x)\n"),                     # not a scan body
    }
    found = by_check(lint(tmp_path, fs, ["host-sync-hazard"]),
                     "host-sync-hazard")
    assert len(found) == 1 and found[0].line == 4


def test_host_sync_pragma_sanctions_line(tmp_path):
    fs = {
        "fakepkg/serving/engine.py": (
            "import numpy as np\n"
            "class Engine:\n"
            "    def step(self):\n"
            "        cache, tok = self._step_jit(1)\n"
            "        tok = np.asarray(tok)"
            "  # graftlint: disable=host-sync-hazard\n"
            "        return int(tok[0])\n"),
    }
    assert lint(tmp_path, fs, ["host-sync-hazard"]) == []


# -----------------------------------------------------------------------------------
# retrace-hazard
# -----------------------------------------------------------------------------------


def test_retrace_immediate_invoke_true_positive(tmp_path):
    fs = {
        "fakepkg/serving/sampler.py": (
            "import jax\n"
            "def sample(params, key):\n"
            "    return jax.jit(lambda k: k)(key)\n"),
    }
    found = by_check(lint(tmp_path, fs, ["retrace-hazard"]), "retrace-hazard")
    assert len(found) == 1 and found[0].line == 3
    assert "fresh wrapper" in found[0].message


def test_retrace_jit_in_loop_true_positive(tmp_path):
    fs = {
        "fakepkg/serving/sweep.py": (
            "import jax\n"
            "def sweep(fns):\n"
            "    out = []\n"
            "    for fn in fns:\n"
            "        out.append(jax.jit(fn))\n"
            "    return out\n"),
    }
    found = by_check(lint(tmp_path, fs, ["retrace-hazard"]), "retrace-hazard")
    assert len(found) == 1
    assert "inside a loop" in found[0].message


def test_retrace_builders_and_memoization_clean(tmp_path):
    fs = {
        "fakepkg/parallel/dp.py": (
            "import jax\n"
            "STEP = jax.jit(lambda x: x)\n"               # module scope: once
            "def make_step(fn):\n"
            "    return jax.jit(fn)\n"                    # builder: caller caches
            "def cached(fn, cache, key):\n"
            "    if key not in cache:\n"
            "        cache[key] = jax.jit(fn)\n"          # memoized: sanctioned
            "    return cache[key]\n"),
    }
    assert lint(tmp_path, fs, ["retrace-hazard"]) == []


def test_retrace_scripts_exempt_from_per_call_rules(tmp_path):
    # One-shot harnesses (tools/, bench*.py) invoke each jit exactly once.
    fs = {
        "tools/bench_thing.py": (
            "import jax\n"
            "def leg(key):\n"
            "    return jax.jit(lambda k: k)(key)\n"),
    }
    assert lint(tmp_path, fs, ["retrace-hazard"]) == []


def test_retrace_unhashable_static_arg(tmp_path):
    fs = {
        "fakepkg/serving/compilecache.py": (
            "import jax\n"
            "def prog(x, sizes):\n"
            "    return x\n"
            "RUN = jax.jit(prog, static_argnames=('sizes',))\n"
            "def call(x):\n"
            "    return RUN(x, sizes=[1, 2])\n"),         # list: unhashable
    }
    found = by_check(lint(tmp_path, fs, ["retrace-hazard"]), "retrace-hazard")
    assert len(found) == 1
    assert "unhashable list" in found[0].message
    # Tuple literal in the same position is hashable: clean.
    fs["fakepkg/serving/compilecache.py"] = \
        fs["fakepkg/serving/compilecache.py"].replace("[1, 2]", "(1, 2)")
    assert lint(tmp_path, fs, ["retrace-hazard"]) == []


# -----------------------------------------------------------------------------------
# machinery: pragmas, baseline, graph
# -----------------------------------------------------------------------------------


def test_parse_pragmas_line_and_file_scopes():
    file_level, by_line = parse_pragmas(
        "# graftlint: disable-file=telemetry-schema\n"
        "x = 1  # graftlint: disable=host-sync-hazard,retrace-hazard\n"
        "y = 2  # ordinary comment\n")
    assert file_level == {"telemetry-schema"}
    assert by_line == {2: {"host-sync-hazard", "retrace-hazard"}}


def test_parse_pragmas_ignores_strings_and_docstrings():
    # Pragma syntax QUOTED in a docstring/string (someone documenting the
    # mechanism) must not disable anything — only real comments count.
    file_level, by_line = parse_pragmas(
        '"""Docs show: # graftlint: disable-file=resolve-guard"""\n'
        's = "# graftlint: disable=backend-purity"\n')
    assert file_level == set() and by_line == {}


def test_docstring_pragma_does_not_suppress(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            '"""Use `# graftlint: disable-file=resolve-guard` to opt out."""\n'
            "def done(fut, v):\n"
            "    fut.set_result(v)\n"),
    }
    assert len(by_check(lint(tmp_path, fs, ["resolve-guard"]),
                        "resolve-guard")) == 1


def test_file_pragma_suppresses_whole_file(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            "# graftlint: disable-file=resolve-guard\n"
            "def done(fut, v):\n"
            "    fut.set_result(v)\n"),
    }
    assert lint(tmp_path, fs, ["resolve-guard"]) == []


def test_baseline_matching_and_staleness(tmp_path):
    fs = {
        "fakepkg/serving/server.py": (
            "def done(fut, v):\n"
            "    fut.set_result(v)\n"),
    }
    findings = lint(tmp_path, fs, ["resolve-guard"])
    assert len(findings) == 1
    f = findings[0]
    stale_entry = {"check": "resolve-guard", "path": "gone.py", "message": "x"}
    baseline = Baseline(path=str(tmp_path / "b.json"), entries=[
        {"check": f.check, "path": f.path, "message": f.message}, stale_entry])
    new, baselined, stale = baseline.split(findings)
    assert new == [] and len(baselined) == 1 and stale == [stale_entry]
    # An un-baselined finding stays new.
    new2, _, _ = Baseline(path="", entries=[stale_entry]).split(findings)
    assert new2 == findings


def test_graph_lazy_vs_toplevel_edges(tmp_path):
    for rel, src in {**BASE_FILES, "fakepkg/mod.py": (
            "import os\n"
            "def f():\n"
            "    import json\n")}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    graph = build_graph(str(tmp_path))
    edges = graph.edges("fakepkg.mod", include_lazy=True)
    assert {(e.target, e.lazy) for e in edges} == {("os", False),
                                                   ("json", True)}
    assert [e.target for e in graph.edges("fakepkg.mod")] == ["os"]


# -----------------------------------------------------------------------------------
# the meta-test + CLI: this repo is clean, and the gate really gates
# -----------------------------------------------------------------------------------


def test_repo_is_clean_under_graftlint():
    """THE gate: zero non-baselined findings on this repository."""
    findings, graph = run_lint(REPO)
    baseline = load_baseline(default_baseline_path(REPO))
    new, _baselined, stale = baseline.split(findings)
    assert new == [], "graftlint findings:\n" + "\n".join(
        f.format() for f in new)
    assert stale == [], f"stale baseline entries: {stale}"
    # Sanity: the scan actually covered the fleet-side modules the rules name.
    for rel in (f"{PKG}/serving/router.py", f"{PKG}/resilience/supervisor.py",
                "tools/serve_loadgen.py"):
        assert graph.module_for_relpath(rel) is not None, rel


def test_registry_and_report_agree():
    """KNOWN_EVENTS is derived, so the footer cannot drift from the emitters."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools", "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    events = __import__(f"{PKG}.utils.telemetry_events",
                        fromlist=["EVENT_KINDS", "KNOWN_EVENTS"])
    assert report.KNOWN_EVENTS == events.KNOWN_EVENTS
    assert set(events.EVENT_KINDS) == set(events.KNOWN_EVENTS)
    assert all(isinstance(v, str) and v for v in events.EVENT_KINDS.values())


def test_cli_exit_codes_and_json(tmp_path):
    env = {**os.environ, "PYTHONPATH": REPO}
    # Clean repo: exit 0.
    ok = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    doc = json.loads(ok.stdout)
    assert doc["ok"] is True and doc["findings"] == []
    assert doc["modules"] > 50
    # Introduce a violation in a fixture repo: exit 1, finding in the JSON.
    for rel, src in {**BASE_FILES, "fakepkg/serving/router.py":
                     "import jax\n"}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    bad = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", str(tmp_path),
         "--json", "--baseline", str(tmp_path / "baseline.json")],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    doc = json.loads(bad.stdout)
    assert doc["ok"] is False
    assert any(f["check"] == "backend-purity" for f in doc["findings"])


def test_cli_update_baseline_roundtrip(tmp_path):
    env = {**os.environ, "PYTHONPATH": REPO}
    for rel, src in {**BASE_FILES, "fakepkg/serving/router.py":
                     "import jax\n"}.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    base = str(tmp_path / "baseline.json")
    wrote = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", str(tmp_path),
         "--baseline", base, "--update-baseline"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    entries = json.loads(open(base).read())
    assert entries and entries[0]["check"] == "backend-purity"
    # Baselined: the same tree now gates green.
    rerun = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", str(tmp_path),
         "--baseline", base],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert rerun.returncode == 0, rerun.stdout + rerun.stderr
    assert "1 baselined" in rerun.stdout


def test_cli_update_baseline_rejects_filtered_run(tmp_path):
    # A filtered run saving the baseline would silently delete every other
    # checker's grandfathered entries.
    env = {**os.environ, "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--checks", "backend-purity",
         "--update-baseline", "--baseline", str(tmp_path / "b.json")],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert r.returncode == 2
    assert "full run" in r.stderr


def test_cli_unknown_check_is_usage_error(tmp_path):
    env = {**os.environ, "PYTHONPATH": REPO}
    r = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--checks", "no-such-check"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert r.returncode == 2
    assert "unknown check" in r.stderr


def test_committed_baseline_ships_empty():
    """The satellite's bar: no grandfathered findings — everything was fixed."""
    baseline = load_baseline(default_baseline_path(REPO))
    assert baseline.entries == []
