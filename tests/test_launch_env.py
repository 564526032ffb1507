"""``repro.launch.env`` — pre-jax environment hygiene.

The contract: ``apply_env`` sets the SNIPPETS run.sh environment
(allocator thresholds, log level, XLA device-count flag, x64 policy)
with **setdefault semantics** — an operator's explicit environment
always wins — and is import-order safe: importing ``repro``,
``repro.launch`` or ``repro.launch.env`` must not import jax (the lazy
package layout exists for exactly this), while calling ``apply_env``
*after* jax was imported warns and changes nothing rather than lying.
The subprocess test proves the full sequence end-to-end: import env
module jax-free, apply, then import jax and observe the virtual device
count the flag requested.
"""
import os
import subprocess
import sys

import pytest

from repro.launch.env import DEFAULT_ENV, apply_env, compile_cache_dir, tcmalloc_note


class TestApplyEnv:
    def test_defaults_set_when_absent(self):
        env = {}
        applied = apply_env(env=env)
        assert env == DEFAULT_ENV == applied

    def test_existing_vars_win(self):
        env = {k: "operator-set" for k in DEFAULT_ENV}
        applied = apply_env(env=env)
        assert applied == {}
        assert all(v == "operator-set" for v in env.values())

    def test_devices_and_x64(self):
        env = {}
        applied = apply_env(devices=8, x64=True, env=env)
        assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"
        assert env["JAX_ENABLE_X64"] == "1"
        assert applied["XLA_FLAGS"] == env["XLA_FLAGS"]

    def test_xla_flags_merged_not_duplicated(self):
        env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
        apply_env(devices=8, extra_xla_flags=("--xla_cpu_foo=1",), env=env)
        # the operator's device count stands; the new flag is appended
        assert env["XLA_FLAGS"] == (
            "--xla_force_host_platform_device_count=4 --xla_cpu_foo=1"
        )
        apply_env(extra_xla_flags=("--xla_cpu_foo=2",), env=env)
        assert env["XLA_FLAGS"].count("--xla_cpu_foo") == 1

    def test_after_jax_import_warns_and_noops(self):
        # this test process imported jax long ago (conftest does)
        assert "jax" in sys.modules
        before = dict(os.environ)
        with pytest.warns(UserWarning, match="after jax was imported"):
            applied = apply_env(devices=2)
        assert applied == {}
        assert dict(os.environ) == before

    def test_tcmalloc_note_respects_existing_preload(self):
        assert tcmalloc_note({"LD_PRELOAD": "/x/libwhatever.so"}) is None
        note = tcmalloc_note({})
        if note is not None:  # only when a system tcmalloc exists
            assert "LD_PRELOAD" in note


class TestImportOrder:
    def test_env_module_imports_jax_free_then_flag_takes_effect(self):
        """The full launcher sequence in a clean interpreter."""
        code = (
            "import sys\n"
            "import repro.launch.env as env\n"
            "import repro, repro.launch\n"
            "assert 'jax' not in sys.modules, 'lazy package pulled jax'\n"
            "applied = env.apply_env(devices=3)\n"
            "assert 'XLA_FLAGS' in applied, applied\n"
            "import jax\n"
            "assert jax.device_count() == 3, jax.device_count()\n"
            "print('OK')\n"
        )
        clean = dict(os.environ)
        for k in ("XLA_FLAGS", "JAX_ENABLE_X64", *DEFAULT_ENV):
            clean.pop(k, None)
        clean["PYTHONPATH"] = os.path.join(
            os.path.dirname(__file__), "..", "src"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env=clean,
        )
        assert proc.returncode == 0, proc.stderr
        assert "OK" in proc.stdout


class TestCompileCache:
    def test_external_dir_kept(self):
        env = {"JAX_COMPILATION_CACHE_DIR": "/srv/jax-cache"}
        assert compile_cache_dir(env) == "/srv/jax-cache"

    def test_fixed_in_checkout_path(self):
        first, second = compile_cache_dir({}), compile_cache_dir({})
        assert first == second
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == os.path.join(repo, ".jax_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
