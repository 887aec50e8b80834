"""chip_smoke.py's contract, rehearsed on CPU, and the compile-cache rule.

The smoke itself only passes on a TPU; what tier-1 can hold is that its
`--dry-run-cpu` rehearsal walks every leg, that without the flag and without
a chip it refuses before doing any work, and that the compile cache stays
where `JAX_COMPILATION_CACHE_DIR` puts it.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over)
    return subprocess.run([sys.executable, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)


def test_dry_run_walks_every_leg():
    r = _run([SMOKE, "--dry-run-cpu"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    lines = r.stdout.splitlines()
    assert lines and all(ln.startswith("DRYRUN platform=cpu ")
                         for ln in lines), r.stdout
    for leg in ("train", "quantized", "predict", "serve", "multichip"):
        assert any(ln.startswith(f"DRYRUN platform=cpu {leg}: ok")
                   for ln in lines), f"no passing {leg!r} line:\n{r.stdout}"
    # a rehearsal is never a result: the stamped last line is not the
    # bare JSON object a chip pass ends with
    assert not lines[-1].startswith("{")
    # with JAX_COMPILATION_CACHE_DIR unset the cache is <checkout>/.jax_cache
    assert any(f"compile cache: dir={os.path.join(ROOT, '.jax_cache')} " in ln
               for ln in lines), r.stdout


def test_refuses_without_a_chip():
    r = _run([SMOKE], JAX_PLATFORMS="cpu")
    assert r.returncode != 0
    assert r.stdout == "", r.stdout
    assert "platform is 'cpu'" in r.stderr, r.stderr[-2000:]


_CACHE_CHILD = """
import sys
sys.path.insert(0, {root!r})
import jax
import lightgbm_tpu as lgb
import numpy as np
print("IMPORT", jax.config.jax_compilation_cache_dir)
X = np.random.default_rng(0).normal(size=(64, 4))
p = {{"objective": "binary", "verbosity": -1, "min_data_in_leaf": 2,
     "tpu_compile_cache_dir": sys.argv[1]}}
lgb.Booster(params=p, train_set=lgb.Dataset(
    X, label=(X[:, 0] > 0).astype(float), params=p))
print("BOOSTER", jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_stays_where_the_environment_puts_it(tmp_path):
    """With the variable set, neither the import hook nor a Booster built
    with tpu_compile_cache_dir re-points the cache (the unset half of the
    rule is read off the dry run above)."""
    placed, other = str(tmp_path / "placed"), str(tmp_path / "option")
    r = _run(["-c", _CACHE_CHILD.format(root=ROOT), other],
             JAX_COMPILATION_CACHE_DIR=placed)
    assert r.returncode == 0, r.stderr[-4000:]
    assert [ln for ln in r.stdout.splitlines()
            if ln.startswith(("IMPORT ", "BOOSTER "))] == [
                f"IMPORT {placed}", f"BOOSTER {placed}"]
