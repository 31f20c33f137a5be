"""chip_smoke.py off the chip: its phases at a tiny size on the CPU backend,
and its refusal to report success anywhere but on a TPU.

The phases are the script's own functions, called with scaled-down widths
(same tensor names and dtypes): they start the same children, pull through
the same fabric and make the same checks — origin served about once,
from_p2p, on-device verify, every tensor typed and placed, samples
bit-exact, the two dfget exit codes — so a broken smoke shows up here, at
no chip time.
"""

import asyncio
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# About 12 MiB: three 4 MiB pieces, 205 + 2 tensors.
TINY = chip_smoke.Widths(hidden=256, vocab=4096, routed=64, shared=2,
                         expert=96, kv_lora=64, heads=4, nope=32, rope=16,
                         v=32)


def _processes_mentioning(text: str) -> list[str]:
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if text in cmdline:
            found.append(f"{pid}: {cmdline[:200]}")
    return found


@pytest.fixture
def home():
    # Not tmp_path: the daemon's unix socket path must stay under 108
    # bytes, and pytest's per-worker directories are long.
    path = tempfile.mkdtemp(prefix="cs_")
    yield path
    shutil.rmtree(path, ignore_errors=True)


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_pass_at_tiny_size_and_leave_no_process(home, chips, capsys):
    import jax

    asyncio.run(asyncio.wait_for(
        chip_smoke.run(TINY, chips, home, jax.devices()), 240))
    out = capsys.readouterr().out
    assert "from_p2p=True" in out and "(1.000x content)" in out, out
    passed = (["phase four chips passed"] if chips == 4 else
              ["phase A (client API) passed", "phase B (CLI) passed"])
    for line in passed:
        assert line in out, out
    # Every child's command line carries the run's home directory.
    deadline = time.monotonic() + 10
    while _processes_mentioning(home) and time.monotonic() < deadline:
        time.sleep(0.2)
    assert _processes_mentioning(home) == []


def test_a_failing_phase_fails_the_run(home, monkeypatch):
    """No except carries on: a check that fails ends the run, named."""
    import jax

    async def broken_phase(fabric, widths, device):
        raise chip_smoke.SmokeFailure("injected")

    monkeypatch.setattr(chip_smoke, "phase_a", broken_phase)
    with pytest.raises(chip_smoke.SmokeFailure,
                       match=r"phase A \(client API\): injected"):
        asyncio.run(asyncio.wait_for(
            chip_smoke.run(TINY, 1, home, jax.devices()), 240))
    assert _processes_mentioning(home) == []


def test_main_reports_success_only_through_its_last_line(monkeypatch, capsys,
                                                         tmp_path):
    """main()'s path after the platform check runs nowhere but on a TPU,
    so run it here once, at the tiny size, with the description of the
    device (and nothing else) replaced in the test."""
    monkeypatch.setattr(chip_smoke, "MOONLIGHT", TINY)
    monkeypatch.setattr(chip_smoke, "describe", lambda devices: {
        "platform": "tpu", "kind": "pretend", "count": len(devices)})
    monkeypatch.setattr(chip_smoke, "scratch_home",
                        lambda: tempfile.mkdtemp(prefix="cs_"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "pretend", "count": 8}}
    assert any("compile requests:" in line for line in lines)


def _run_script(cwd: str, script: str, *args: str, env=None):
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, script, *args], cwd=cwd,
                          capture_output=True, text=True, timeout=120,
                          env=env)
    return proc, time.monotonic() - t0


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_script_on_cpu_exits_1_naming_the_platform(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc, seconds = _run_script(REPO, os.path.join(REPO, "chip_smoke.py"),
                                *args, env=env)
    assert proc.returncode == 1 and seconds < 60, proc.stderr[-800:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "'cpu'" in last["error"]
    assert last["device"]["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repository it must fail, and print no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc, _ = _run_script(str(tmp_path), "chip_smoke.py", env=env)
    assert proc.returncode != 0 and proc.stdout == ""


def test_scratch_home_is_made_by_the_run_and_shared_with_no_other(
        home, monkeypatch):
    """Two runs on one machine never share a DF_HOME, and a run removes
    nothing it did not make: no fixed path, inside or outside the
    checkout."""
    monkeypatch.setattr(chip_smoke, "HERE", home)
    monkeypatch.setattr(tempfile, "tempdir", None)
    bystander = os.path.join(home, ".chip_smoke_home")
    os.mkdir(bystander)
    first, second = chip_smoke.scratch_home(), chip_smoke.scratch_home()
    assert first != second and os.path.isdir(bystander)
    assert {os.path.dirname(first), os.path.dirname(second)} == {home}

    # A checkout too deep for the daemon's socket: under TMPDIR instead.
    deep = os.path.join(home, "d" * 80)
    os.mkdir(deep)
    monkeypatch.setattr(chip_smoke, "HERE", deep)
    monkeypatch.setenv("TMPDIR", home)
    monkeypatch.setattr(tempfile, "tempdir", None)      # cached by the above
    assert os.path.dirname(chip_smoke.scratch_home()) == home
    # Neither is short enough: the run fails and says why; no /tmp literal.
    monkeypatch.setattr(tempfile, "tempdir", None)
    monkeypatch.setenv("TMPDIR", deep)
    with pytest.raises(chip_smoke.SmokeFailure, match="socket path"):
        chip_smoke.scratch_home()


def test_object_is_one_real_shard():
    obj = chip_smoke.checkpoint(chip_smoke.MOONLIGHT)
    sizes = {n: e - b for n, (b, e) in obj.spans.items()}
    # 205 of the model, and the two tensors of random bytes behind them.
    assert len(sizes) == 207
    extra = {n: sizes.pop(n) for n in list(sizes) if n.startswith("smoke.")}
    assert len(sizes) == 205 and sum(extra.values()) < 4 << 20
    assert max(obj.spans[n][1] for n in sizes) == min(
        obj.spans[n][0] for n in extra)
    assert obj.length >= 1.7 * 2**30
    assert sizes["model.embed_tokens.weight"] == 163840 * 2048 * 2
    assert max(sizes.values()) == 640 << 20
    experts = [s for n, s in sizes.items() if ".mlp.experts." in n]
    assert len(experts) == 192 and set(experts) == {1408 * 2048 * 2}
    assert obj.data_start % 4 == 2


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    import jax

    from dragonfly2_tpu.ops import compile_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before[0]
        # Sub-second view programs are kept wherever the cache is.
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        placed = compile_cache.place_compile_cache()
        assert placed == os.path.join(REPO, ".jax_cache")
        assert placed == compile_cache.place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
