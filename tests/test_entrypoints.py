"""Driver entry-point smoke tests: __graft_entry__.py must keep working (the
driver's compile checks run through it), the compile cache is placed from
outside, and the parents that spawn chip-holding children stay off JAX.

All run in subprocesses with JAX_PLATFORMS=cpu."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, extra_env=None, timeout=1500):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.update(extra_env or {})
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_compile_cache_placed_from_outside(tmp_path):
    """vitax.platform.setup_compile_cache: JAX_COMPILATION_CACHE_DIR wins and
    nothing is set in code; without it the fixed in-checkout directory."""
    code = ("import jax\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "from vitax.platform import setup_compile_cache\n"
            "print(setup_compile_cache())\n"
            "print(before == jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    outside = str(tmp_path / "placed")
    for env_dir, want in ((outside, outside),
                          (None, os.path.join(REPO, ".jax_cache"))):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        returned, untouched, configured = r.stdout.split()
        assert returned == configured == want
        assert (untouched == "True") == bool(env_dir)


def test_spawning_parents_never_initialize_a_backend():
    """A chip belongs to one process at a time: the supervisor and the fleet
    replica manager start children that need it, so importing and using the
    parents' own code paths must leave JAX's backends uninitialized."""
    code = ("import tempfile\n"
            "import vitax.supervise as sup\n"
            "import vitax.serve.fleet.replica as rep\n"
            "import vitax.serve.fleet.__main__\n"
            "d = tempfile.mkdtemp()\n"
            "sup.run_progress(d, ''); sup.checkpoint_topology(d)\n"
            "sup.Supervisor(['true'], ckpt_dir=d)\n"
            "rep.ReplicaManager()\n"
            "from jax._src import xla_bridge\n"
            "print('initialized', xla_bridge.backends_are_initialized())\n")
    r = _run([sys.executable, "-c", code], timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "initialized False" in r.stdout


@pytest.mark.slow
def test_graft_dryrun_multichip():
    r = _run([sys.executable, "-c",
              "import __graft_entry__ as g; g.dryrun_multichip(8)"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "dryrun_multichip ok" in r.stdout


@pytest.mark.slow
def test_graft_entry_compiles_single_chip():
    r = _run([sys.executable, "-c", (
        "import jax, __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn).lower(*args).compile()(*args)\n"
        "print('entry ok', out.shape)\n")],
        extra_env={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "entry ok" in r.stdout
