"""The optimized HLO of the epoch program a benchmark cell compiles, as a sha256 of its text with
each instruction's `metadata={...}` (op_name, source file and line) dropped: benchmark/run.py
in-process from the tree given, `telemetry.aot_compile` wrapped before the driver wraps it, and the
run ended as soon as the text is hashed (no window is measured; nothing here is a metric).
Two trees whose lines agree ran the compiler on the same program and got the same executable.
usage (on the chip, both trees from one path: a kernel's Mosaic module names its source file):
       python3 hlo_hash.py <repo root to run from> <cell> <seed> <out.jsonl>"""
import gzip, hashlib, json, os, re, runpy, sys, time
T0 = time.perf_counter()
root, cell, seed, out = os.path.realpath(sys.argv[1]), sys.argv[2], sys.argv[3], os.path.realpath(sys.argv[4])
os.chdir(root)
sys.path.insert(0, root)
from csed_514_project_distributed_training_using_pytorch_tpu.utils import telemetry as T
assert os.path.realpath(T.__file__).startswith(root + os.sep), T.__file__
original = T.aot_compile


def bare(text: str) -> str:
    """The text without what names source lines: each instruction's metadata, and the tables of
    files, functions, locations and stack frames between the module's line and its first
    computation (a function's name is there: `main.<locals>.lm_loss` at the parent)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    start = text.find("\nFileNames\n")
    if start >= 0:
        text = text[:start] + text[text.index("\n\n", text.index("\nStackFrames\n")):]
    return text


def hashed(jit_fn, *args):
    compiled, aot = original(jit_fn, *args)
    cold_s = time.perf_counter() - T0   # process start to the epoch program compiled, every cache empty
    text = compiled.as_text()
    whole = bare(text)
    # a Pallas kernel's serialized Mosaic module carries the call stack of the line that called it,
    # the trainer's frames among them; the kernels are ops/'s, which this PR does not touch
    no_bodies = re.sub(r'(\\?"body\\?": ?\\?")[^"\\]*', r"\1", whole)
    line = {"cell": cell, "seed": int(seed), "module": aot["scopes"]["module"],
            "instructions": len(aot["scopes"]["ops"]), "hlo_bytes": len(text), "bare_bytes": len(whole),
            "bare_hlo_sha256": hashlib.sha256(whole.encode()).hexdigest(),
            "bare_bytes_without_kernel_bodies": len(no_bodies),
            "bare_hlo_without_kernel_bodies_sha256": hashlib.sha256(no_bodies.encode()).hexdigest(),
            "tpu_custom_calls": whole.count('custom_call_target="tpu_custom_call"'),
            "flops": aot["flops"], "bytes_accessed": aot["bytes_accessed"],
            "start_to_compiled_s": cold_s, "lower_s": aot["lower_s"], "compile_s": aot["compile_s"]}
    with open(out, "a") as fh:
        fh.write(json.dumps(line) + "\n")
    with gzip.open(out + f".{cell}.hlo.txt.gz", "wt") as fh:     # to diff, should the hashes differ
        fh.write(no_bodies)
    print(json.dumps(line), flush=True)
    os._exit(0)     # the text is all this run is for; the benchmark's window never opens


T.aot_compile = hashed
sys.argv = ["benchmark/run.py", "--workload", cell, "--seed", seed, "--seconds", "40", "--trace", "0"]
runpy.run_path(os.path.join(root, "benchmark", "run.py"), run_name="__main__")
