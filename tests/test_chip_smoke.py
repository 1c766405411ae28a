"""What keeps the chip path honest, checked on the CPU (ISSUE 21):

- the compile-cache placement rule (dalle_pytorch_tpu/compile_cache.py):
  ``JAX_COMPILATION_CACHE_DIR`` wins and code sets no other directory;
  unset, one fixed path under the checkout; the test harness follows the
  same rule;
- ``chip_smoke.py`` refuses to run without an accelerator, before any
  phase, and its parent never imports jax (one process per chip);
- the one platform decision of ops/ lives in ``kv_policy.on_tpu`` — no
  attention call site computes its own ``interpret=``;
- nothing in the tree assumes where the checkout sits.

The chip run itself cannot be a tier-1 test; ``python chip_smoke.py`` on a
TPU is that test, and CHANGES.md records its passes.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from dalle_pytorch_tpu import compile_cache  # noqa: E402

# what .gitignore lists: build and cache output is not the tree
SKIP_DIRS = {".git", ".jax_cache", "chiprun_out", "__pycache__", ".pytest_cache",
             "build", "dist", "rainbow_demo"}


def tracked_py():
    """Every .py under the checkout that is not build or cache output (the
    driver's checkout may have no .git, so this walks instead of asking
    git)."""
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        for f in files:
            if f.endswith(".py"):
                yield Path(root) / f


# ------------------------------------------------------------ compile cache


class TestCompileCache:
    def test_env_var_wins_and_code_sets_nothing(self, monkeypatch):
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/placed")

        def forbidden(name, value):
            raise AssertionError(f"code set {name}={value!r} under an env-placed cache")

        monkeypatch.setattr(jax.config, "update", forbidden)
        assert compile_cache.enable_compile_cache() == "/somewhere/placed"
        assert compile_cache.enable_compile_cache(default="/ignored") == "/somewhere/placed"

    def test_unset_uses_the_fixed_path_under_the_checkout(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR)
        calls = []
        monkeypatch.setattr(jax.config, "update", lambda n, v: calls.append((n, v)))
        want = str(REPO / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert compile_cache.enable_compile_cache() == want  # same every time
        assert calls == [("jax_compilation_cache_dir", want)] * 2
        assert compile_cache.cache_dir(default="/d") == "/d"

    def test_path_is_not_made_from_tempfile_pid_or_clock(self):
        src = Path(compile_cache.__file__).read_text()
        code = src.split('"""', 2)[2]  # past the module docstring
        for word in ("tempfile", "getpid", "time", "uuid", "random"):
            assert word not in code, word

    def test_harness_follows_the_rule(self):
        # tests/conftest.py: the variable if set, else tests/.jax_cache —
        # exported, so CLI subprocesses and workers share the directory
        placed = os.environ[compile_cache.ENV_VAR]
        assert jax.config.jax_compilation_cache_dir == placed

    def test_only_the_helper_sets_the_directory(self):
        needle = '"jax_compilation_' + 'cache_dir"'
        setters = [
            str(p.relative_to(REPO)) for p in tracked_py()
            if needle in p.read_text() and "config.update" in p.read_text()
            and p.name != "test_chip_smoke.py"
        ]
        assert setters == ["dalle_pytorch_tpu/compile_cache.py"], setters

    @pytest.mark.parametrize("script", [
        "train_vae.py", "train_dalle.py", "train_clip.py", "train_lm.py",
        "generate.py", "chip_smoke.py",
    ])
    def test_entry_points_call_the_helper(self, script):
        assert "enable_compile_cache()" in (REPO / script).read_text()


# --------------------------------------------------------------- chip_smoke


def run_smoke(cwd, script):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


class TestChipSmoke:
    def test_refuses_without_an_accelerator_before_any_phase(self):
        proc = run_smoke(REPO, REPO / "chip_smoke.py")
        assert proc.returncode not in (0, 2), proc.stdout + proc.stderr
        assert "no accelerator" in proc.stderr and "'cpu'" in proc.stderr
        assert "phase" not in proc.stdout and '"ok"' not in proc.stdout

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
        proc = run_smoke(tmp_path, tmp_path / "chip_smoke.py")
        assert proc.returncode == 2, proc.stdout + proc.stderr
        assert "train_vae.py" in proc.stderr
        assert proc.stdout == ""

    def test_importing_it_leaves_jax_out(self):
        proc = subprocess.run(
            [sys.executable, "-c",
             "import sys, chip_smoke; sys.exit('jax' in sys.modules)"],
            cwd=REPO, capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_an_interpreted_kernel_fails_the_route_check(self):
        rep = {"routes": [
            {"site": "forward/full", "impl": "fused_qkv_flash", "interpret": True},
        ]}
        with pytest.raises(SystemExit, match="interpreted"):
            chip_smoke.check_routes("p", rep, [])
        rep["routes"][0]["interpret"] = False
        chip_smoke.check_routes(
            "p", rep, [("forward/full", "fused_qkv_flash", False)]
        )
        with pytest.raises(SystemExit, match="expected attention"):
            chip_smoke.check_routes(
                "p", rep, [("ragged_block/full", "ragged_paged_kernel", False)]
            )

    def test_mosaic_modules_reads_the_ir_dump(self, tmp_path):
        (tmp_path / "jax_ir0003_jit_train_step_compile.mlir").write_text(
            "stablehlo.custom_call @tpu_custom_call(\n" * 3
        )
        (tmp_path / "jax_ir0004_jit__decode_jit_compile.mlir").write_text(
            "stablehlo.dot_general\n"
        )
        rep = chip_smoke.ChildReport.__new__(chip_smoke.ChildReport)
        rep.ir_dir = tmp_path
        assert rep.mosaic_modules() == {"jit_train_step": 3}

    def test_losses_come_from_the_flight_recorder(self, tmp_path):
        recs = [
            {"ts": 0.0, "ph": "B", "name": "train.step", "id": 1, "step": 0},
            {"ts": 1.0, "ph": "E", "name": "train.step", "id": 1, "loss": 2.5},
            {"ts": 1.5, "ph": "I", "name": "train.nan_skip"},
            {"ts": 2.0, "ph": "E", "name": "train.step", "id": 2, "loss": 1.5},
        ]
        (tmp_path / "flight-1.jsonl").write_text(
            "\n".join(json.dumps(r) for r in recs) + "\n"
        )
        assert chip_smoke.read_losses(tmp_path) == [2.5, 1.5]


# ------------------------------------------------------- no hidden fallback


class TestOnePlatformDecision:
    def test_interpret_is_decided_in_kv_policy_only(self):
        ops = REPO / "dalle_pytorch_tpu" / "ops"
        for p in ops.glob("*.py"):
            text = p.read_text()
            if p.name == "kv_policy.py":
                assert "default_backend()" in text
                continue
            assert ".platform" not in text, p.name
            assert "default_backend" not in text, p.name

    def test_cpu_interprets_and_routes_are_recorded_once(self, monkeypatch):
        from dalle_pytorch_tpu.ops import kv_policy

        assert kv_policy.on_tpu() is False
        assert kv_policy.pallas_interpret() is True
        monkeypatch.setattr(kv_policy, "ROUTE_LOG", [])
        kv_policy.record_route("forward/full", "fused_qkv_flash", True)
        kv_policy.record_route("forward/full", "fused_qkv_flash", True)
        kv_policy.record_route("decode_token", "cache_block_attend")
        assert kv_policy.ROUTE_LOG == [
            {"site": "forward/full", "impl": "fused_qkv_flash", "interpret": True},
            {"site": "decode_token", "impl": "cache_block_attend", "interpret": None},
        ]

    def test_cpu_gates_assign_the_platform(self):
        for name in ("lint.py", "serve_smoke.py", "telemetry_smoke.py",
                     "chaos_soak.py", "traffic_sim.py"):
            text = (REPO / "tools" / name).read_text()
            assert 'os.environ["JAX_PLATFORMS"] = "cpu"' in text, name
            assert 'setdefault("JAX_PLATFORMS"' not in text, name


def test_no_file_assumes_where_the_checkout_sits():
    needle = '"/root' + '/repo"'
    assert [str(p) for p in tracked_py() if needle in p.read_text()] == []
