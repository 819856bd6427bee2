"""``chip_smoke.py`` and the entry points' compile cache, off the chip.

The smoke itself needs a TPU; here it must refuse to run, in one line,
without printing a result. The compile-cache helper runs in a
subprocess so its process-global setting never reaches other tests.
"""

import importlib.util
import json
import shutil
from pathlib import Path

from _subproc import run_snippet

ROOT = Path(__file__).resolve().parent.parent


def _load(path: Path):
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_fails_without_a_tpu(capsys):
    rc = _load(ROOT / "chip_smoke.py").main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert err.count("\n") == 1 and "no TPU" in err


def test_chip_smoke_fails_outside_a_checkout(tmp_path, capsys):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    rc = _load(tmp_path / "chip_smoke.py").main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert err.count("\n") == 1 and "no repro package" in err


_CACHE_SNIPPET = """
import json, jax
from repro.launch.compile_cache import enable_compile_cache
got = enable_compile_cache()
{compile}
print(json.dumps({{"got": got,
                  "config": jax.config.jax_compilation_cache_dir}}))
"""
_COMPILE = """
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda v: v * 2 + 1)(jax.numpy.arange(7.0)).block_until_ready()
"""


def _cache_run(compile_code: str = "", env=None) -> dict:
    proc = run_snippet(_CACHE_SNIPPET.format(compile=compile_code),
                       devices=None, env=env)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_keeps_the_env_directory(tmp_path):
    cache = tmp_path / "cache"
    res = _cache_run(_COMPILE, env={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert res["got"] == res["config"] == str(cache)
    assert any(cache.iterdir()), "no cache entry landed in the env dir"


def test_compile_cache_defaults_to_the_checkout():
    res = _cache_run()
    assert res["got"] == res["config"] == str(ROOT / ".jax_cache")
