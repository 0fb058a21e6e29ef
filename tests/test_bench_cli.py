"""What replaced bench.py's retry/probe/fallback loop (PR 21), pinned host-only:

- ``bench.py`` and ``chip_smoke.py`` exit non-zero and name the platform when it is not
  ``tpu`` — no CPU measurement is ever printed under the published metric's name;
- the compile-cache owner leaves ``jax.config``'s cache directory alone when
  ``JAX_COMPILATION_CACHE_DIR`` is set, otherwise returns the same in-checkout path from
  any CWD, and raises when it cannot enable;
- importing the package (and the launcher, and ``chip_smoke.py``) does not import jax;
- ``chip_smoke.py``'s parent stops the processes it starts and turns any child failure
  into its own.

Nothing here compiles; the suite's CPU backend is already up when these run.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
PKG = "csed_514_project_distributed_training_using_pytorch_tpu"


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test", os.path.join(REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them: the owner under
    test must not switch a persistent cache on for the rest of the suite."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


# ------------------------------------------------------------------ bench.py


def test_bench_refuses_the_published_protocol_off_chip(monkeypatch, capsys,
                                                       config_updates):
    """``JAX_PLATFORMS=cpu python bench.py``: exit 1, a one-line reason naming the
    platform on stderr, nothing on stdout — and it refused BEFORE enabling the
    compile cache or loading data."""
    bench = _load("bench")
    monkeypatch.delenv("BENCH_MAX_TRAIN_EXAMPLES", raising=False)
    assert bench.main() == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert err.count("\n") == 1 and "'cpu'" in err and "not 'tpu'" in err
    assert config_updates == []


def test_bench_measure_raises_typed_not_on_chip(monkeypatch):
    bench = _load("bench")
    monkeypatch.setenv("BENCH_MAX_TRAIN_EXAMPLES", "0")
    with pytest.raises(bench.NotOnChip, match="device metric"):
        bench.measure()


def test_bench_has_no_retry_or_fallback_knobs():
    """One process: the probe/patient/fallback machinery and its knobs are gone, and
    the script starts no child."""
    text = open(os.path.join(REPO, "bench.py")).read()
    # Spelled in halves so a repo-wide grep for the retired names finds nothing.
    for gone in ("BENCH_TPU_" "RETRY", "BENCH_ATTEMPT_" "TIMEOUT",
                 "BENCH_PROBE_" "TIMEOUT", "BENCH_" "WEDGE", "fallback_" "reason",
                 "last_hardware_" "capture", "subprocess", "--inner"):
        assert gone not in text, gone
    import re
    assert re.findall(r'environ\.get\("(BENCH_\w+)"', text) == [
        "BENCH_MAX_TRAIN_EXAMPLES", "BENCH_UNROLL", "BENCH_PREGATHER",
        "BENCH_TIMED_EPOCHS"]


def test_bench_emits_typed_strict_json_telemetry_event(capsys, tmp_path):
    """The bench artifact is one `"event": "bench"` line in the utils/telemetry.py
    schema (non-finite floats become null), and --telemetry PATH appends the same
    line to a JSONL file for tools/telemetry_report.py."""
    bench = _load("bench")
    tele = tmp_path / "sub" / "tele.jsonl"
    bench._emit({"metric": "m", "value": 1.5, "final_train_loss": float("nan")},
                str(tele))
    payload = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert payload == {"metric": "m", "value": 1.5, "final_train_loss": None,
                       "event": "bench"}
    assert [json.loads(l) for l in open(tele)] == [payload]


# ------------------------------------------------------------------ chip_smoke.py


def test_chip_smoke_names_the_platform_when_it_is_not_tpu(tmp_path):
    smoke = _load("chip_smoke")
    with pytest.raises(smoke.SmokeFailure, match="platform is cpu, not tpu"):
        smoke._tpu_devices()
    with pytest.raises(smoke.SmokeFailure, match="platform is cpu, not tpu"):
        smoke.phase_devices(str(tmp_path))
    assert os.listdir(tmp_path) == []           # no result file for a refusal


def test_chip_smoke_parent_does_not_import_jax():
    """A parent that has touched jax holds the chip its children need."""
    rc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; chip_smoke.phases_for(4, '/w'); "
         "sys.exit('jax' in sys.modules)"], cwd=REPO).returncode
    assert rc == 0


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "not next to this script" in proc.stderr


def test_chip_smoke_phase_list_grows_only_with_the_machine():
    """One chip: the five required phases. Four chips: the same five first, then the
    four multi-chip ones. Every in-process phase is a ``--phase`` child of this same
    script, never run in the parent."""
    smoke = _load("chip_smoke")
    one = [name for name, _, _ in smoke.phases_for(1, "/w")]
    four = [name for name, _, _ in smoke.phases_for(4, "/w")]
    assert one == ["devices", "train_distributed", "train_lm", "server", "kernels"]
    assert four == one + ["placement", "train_lm_tp", "smoke", "smoke_launch"]
    for name, argv, _ in smoke.phases_for(4, "/w"):
        if name in smoke.IN_PROCESS_PHASES:
            assert argv[1:] == ["--phase", name, "--work", "/w"]
        else:
            assert argv[:2] == ["-m", f"{PKG}.train."
                                + {"train_distributed": "distributed",
                                   "train_lm": "lm", "train_lm_tp": "lm",
                                   "smoke": "smoke", "smoke_launch": "launch"}[name]]
    assert set(smoke.IN_PROCESS_PHASES) == {"devices", "server", "kernels",
                                            "placement"}


def test_chip_smoke_sets_no_platform_and_no_xla_flags():
    text = open(os.path.join(REPO, "chip_smoke.py")).read()
    code = text[text.index('"""', 10) + 3:]              # past the module docstring
    for forbidden in ("JAX_PLATFORMS", "XLA_FLAGS", "jax_platforms"):
        assert forbidden not in code, forbidden


def test_chip_smoke_child_failure_is_the_scripts_failure(tmp_path):
    smoke = _load("chip_smoke")
    with pytest.raises(smoke.SmokeFailure, match=r"exited 3(.|\n)*went wrong"):
        smoke._run_phase("p", ["-c", "import sys; print('went wrong'); sys.exit(3)"],
                         str(tmp_path), timeout=30)
    # A phase that checked and refused is reported by its own one-line reason.
    with pytest.raises(smoke.SmokeFailure, match=r"^phase p FAILED: no chip$"):
        smoke._run_phase(
            "p", ["-c", "import sys; print('noise'); "
                        "print('chip_smoke: phase p FAILED: no chip'); sys.exit(1)"],
            str(tmp_path), timeout=30)


def test_chip_smoke_last_line_is_the_result_object_and_nothing_more(
        monkeypatch, capsys, tmp_path):
    """The driver reads the last stdout line and refuses any key beyond ``ok`` and
    ``device`` {platform, kind, count}; the phases and ``"claim": null`` ride the
    summary line before it. Phases faked: two children that do nothing."""
    smoke = _load("chip_smoke")
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(smoke, "phases_for", lambda chips, work: [
        ("devices", ["-c", "pass"],
         lambda w, c: {"device": dict(device), "versions": {"jax": "x"}}),
        ("train_distributed", ["-c", "pass"],
         lambda w, c: {"native_loader": "native", "compile_s": 1.0, "run_s": 2.0}),
    ])
    (tmp_path / PKG).mkdir()
    monkeypatch.setattr(smoke, "ROOT", str(tmp_path))       # logs land under tmp
    monkeypatch.setattr(sys, "path", list(sys.path))
    assert smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    prefix = "chip_smoke: summary: "
    assert lines[-2].startswith(prefix) and lines[-2].endswith('"claim": null}')
    summary = json.loads(lines[-2][len(prefix):])
    assert summary["chips"] == 1 and list(summary["phases"]) == ["train_distributed"]


def test_chip_smoke_stops_every_process_a_timed_out_phase_started(tmp_path):
    smoke = _load("chip_smoke")
    pid_file = tmp_path / "grandchild.pid"
    child = (
        "import subprocess, sys, time; "
        "p = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)']); "
        f"open({str(pid_file)!r}, 'w').write(str(p.pid)); time.sleep(60)")
    with pytest.raises(smoke.SmokeFailure, match="exceeded"):
        smoke._run_phase("hang", ["-c", child], str(tmp_path), timeout=0.3)
    grandchild = int(pid_file.read_text())

    def state():         # None once it is gone
        try:
            with open(f"/proc/{grandchild}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0]
        except FileNotFoundError:
            return None

    # gone, or a zombie waiting for init: either way stopped. SIGKILL is already
    # sent when _run_phase returns; a busy machine may take a moment to deliver it.
    deadline = time.monotonic() + 5.0
    while state() not in (None, "Z") and time.monotonic() < deadline:
        time.sleep(0.02)
    assert state() in (None, "Z")


# ------------------------------------------------------------------ compile cache


def _owner():
    from csed_514_project_distributed_training_using_pytorch_tpu.utils import (
        compile_cache,
    )
    return compile_cache


def test_compile_cache_env_var_leaves_jax_config_dir_alone(monkeypatch, tmp_path,
                                                           config_updates):
    """Placed from outside: jax reads JAX_COMPILATION_CACHE_DIR itself, so the owner
    returns it and issues NO jax_compilation_cache_dir update of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert _owner().enable_compile_cache() == str(tmp_path / "placed")
    assert [name for name, _ in config_updates] == [
        "jax_persistent_cache_min_compile_time_secs"]
    assert not (tmp_path / "placed").exists()   # the owner did not even create it


def test_compile_cache_default_is_one_in_checkout_path_from_any_cwd(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for cwd in (tmp_path, REPO):
        monkeypatch.chdir(cwd)
        paths.append(_owner().enable_compile_cache())
    assert paths == [os.path.join(REPO, ".jax_cache")] * 2
    assert config_updates.count(("jax_compilation_cache_dir", paths[0])) == 2
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read().split()


def test_compile_cache_that_cannot_enable_raises(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def denied(*a, **k):
        raise PermissionError("read-only checkout")

    monkeypatch.setattr(os, "makedirs", denied)
    with pytest.raises(PermissionError):
        _owner().enable_compile_cache()
    assert config_updates == []


def test_only_the_owner_names_the_cache_dir_option():
    """No other code path calls jax.config.update("jax_compilation_cache_dir", …) or
    assigns the variable — a cache placed from outside is the only cache."""
    import re

    sets_it = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir|set_cache_dir\(|"""
        r"""["']JAX_COMPILATION_CACHE_DIR["']\]\s*=|"""
        r"""setdefault\(\s*["']JAX_COMPILATION_CACHE_DIR""")
    owner = os.path.join(PKG, "utils", "compile_cache.py")
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (".git", "tests", "bench_results",
                                                "__pycache__", ".jax_cache",
                                                "chiprun_out", "_scratch")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), REPO)
            if f.endswith(".py") and rel != owner and sets_it.search(
                    open(os.path.join(REPO, rel), encoding="utf-8").read()):
                offenders.append(rel)
    assert offenders == []
    assert sets_it.search(open(os.path.join(REPO, owner)).read())   # the regex bites


# ------------------------------------------------------------------ imports


def test_importing_the_package_does_not_import_jax():
    """The PJRT plug-in shim is gone from ``__init__``: the package, the launcher and
    the cache owner import without jax (fleet parents stay backend-free), and the
    lazy exports still resolve."""
    code = (f"import sys, {PKG} as p, {PKG}.train.launch, "
            f"{PKG}.utils.compile_cache; "
            "assert 'jax' not in sys.modules, 'jax imported'; "
            "assert p.__version__ and 'Net' in dir(p)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_bench_attention_row_schema(monkeypatch, capsys, tmp_path):
    """The attention bench's row contract (r4 verdict item 2): roofline fields
    per impl, causal-aware model FLOPs, converged flags, speedup — pinned with
    the measurement faked so the schema test costs milliseconds."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_attention_under_test",
        os.path.join(os.path.dirname(__file__), os.pardir, "bench_attention.py"))
    ba = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ba)

    monkeypatch.setattr(ba, "_measure", lambda fn, q, k, v: (0.5, True))
    monkeypatch.setattr(sys, "argv",
                        ["bench_attention.py", "--seq-lens", "256",
                         "--out", str(tmp_path / "rows.jsonl")])
    assert ba.main() == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    s = 256
    pairs = s * (s + 1) // 2                      # causal attended pairs
    assert row["fwdbwd_model_flops"] == 3 * 4 * ba.B * ba.H * ba.D * pairs
    assert row["flash_fwdbwd_s"] == 0.5 and row["dense_fwdbwd_s"] == 0.5
    assert row["flash_converged"] is True and row["dense_converged"] is True
    assert row["flash_achieved_flops_per_s"] == round(
        row["fwdbwd_model_flops"] / 0.5)
    assert row["dense_achieved_flops_per_s"] == row["flash_achieved_flops_per_s"]
    # CPU run: no bf16 peak — explicit nulls, not missing keys.
    assert row["flash_pct_of_bf16_peak"] is None
    assert row["dense_pct_of_bf16_peak"] is None
    assert row["speedup_flash_vs_dense"] == 1.0
    assert (tmp_path / "rows.jsonl").exists()


def test_bench_attention_windowed_flops_accounting():
    """_attended_pairs: the causal+window closed form equals brute-force counting."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_attention_under_test2",
        os.path.join(os.path.dirname(__file__), os.pardir, "bench_attention.py"))
    ba = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ba)

    import numpy as np
    for s, w in ((8, None), (8, 3), (16, 16), (16, 40), (5, 1)):
        q = np.arange(s)[:, None]
        k = np.arange(s)[None, :]
        visible = (q >= k) & ((q - k) < (w or s))
        assert ba._attended_pairs(s, w) == int(visible.sum()), (s, w)
