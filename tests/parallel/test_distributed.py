"""Multi-host bootstrap: two OS processes join one JAX multi-controller
runtime via oryx config and run a cross-process reduction (the
TPU-pod-slice topology, exercised on CPU)."""

import contextlib
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent

_PROC = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    from oryx_tpu.common import config as C
    from oryx_tpu.parallel.distributed import maybe_initialize

    pid, port = int(sys.argv[1]), sys.argv[2]
    cfg = C.get_default().with_overlay(
        'oryx.batch.compute.distributed {{\\n'
        f'  coordinator-address = "127.0.0.1:{{port}}"\\n'
        '  num-processes = 2\\n'
        f'  process-id = {{pid}}\\n'
        '}}'
    )
    assert maybe_initialize(cfg)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.process_count() == 2
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), np.ones((1,), np.float32) * (pid + 1), (2,)
    )
    total = jax.jit(lambda x: jnp.sum(x), out_shardings=NamedSharding(mesh, P()))(arr)
    assert float(total) == 3.0, float(total)
    print("DIST_OK", pid)
    """
).format(repo=str(REPO))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_runtime(tmp_path):
    script = tmp_path / "proc.py"
    script.write_text(_PROC)
    port = str(_free_port())
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin:/usr/local/bin"}
    import os

    env.update({k: v for k, v in os.environ.items() if k not in env})
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # no virtual device splitting across processes
    procs = [
        subprocess.Popen(
            [sys.executable, "-u", str(script), str(pid), port],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
        )
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"DIST_OK {pid}" in out


@contextlib.contextmanager
def _restoring_cache_state():
    """jax config is process-global: restore it so later tests don't
    write a persistent cache under a tmp_path."""
    import jax

    from oryx_tpu.parallel import distributed

    prev = (
        distributed._cache_dir,
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    distributed._cache_dir = None
    try:
        yield
    finally:
        distributed._cache_dir = prev[0]
        jax.config.update("jax_compilation_cache_dir", prev[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[2])


def test_compile_cache_dir_resolution(tmp_path, monkeypatch):
    """One rule: $JAX_COMPILATION_CACHE_DIR wins and means "set nothing in
    code"; else oryx.compute.compile-cache-dir; else the fixed
    <checkout>/.jax_cache."""
    from oryx_tpu.common import config as C
    from oryx_tpu.parallel import distributed

    cfg = C.get_default().with_overlay(
        f'oryx.compute.compile-cache-dir = "{tmp_path}/xla-cache"'
    )
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert distributed.compile_cache_dir(C.get_default()) == str(REPO / ".jax_cache")
    assert distributed.compile_cache_dir() == str(REPO / ".jax_cache")
    assert distributed.compile_cache_dir(cfg) == f"{tmp_path}/xla-cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env-cache"))
    assert distributed.compile_cache_dir(cfg) is None
    assert distributed.compile_cache_dir() is None


def test_compile_cache_env_leaves_jax_config_alone(tmp_path, monkeypatch):
    import jax

    from oryx_tpu.common import config as C
    from oryx_tpu.parallel import distributed

    cfg = C.get_default().with_overlay(
        f'oryx.compute.compile-cache-dir = "{tmp_path}/xla-cache"'
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env-cache"))
    with _restoring_cache_state():
        before = jax.config.jax_compilation_cache_dir
        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a, **k: updates.append(a)
        )
        distributed.enable_compile_cache(cfg)
        distributed.enable_compile_cache()
        assert updates == []
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "xla-cache").exists()


def test_compile_cache_config_key_and_cpu_default(tmp_path, monkeypatch):
    """The config key is honoured on any backend; the default directory
    is not applied on the CPU (XLA:CPU entries are machine-specific and
    the checkout travels between machines)."""
    import jax

    from oryx_tpu.common import config as C
    from oryx_tpu.parallel import distributed

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    with _restoring_cache_state():
        before = jax.config.jax_compilation_cache_dir
        distributed.enable_compile_cache(C.get_default())
        assert jax.config.jax_compilation_cache_dir == before
        assert distributed._cache_dir is None

        d = tmp_path / "xla-cache"
        cfg = C.get_default().with_overlay(
            f'oryx.compute.compile-cache-dir = "{d}"'
        )
        distributed.enable_compile_cache(cfg)
        assert jax.config.jax_compilation_cache_dir == str(d)
        assert d.is_dir()
        # idempotent: a second call (other layer in-process) is a no-op
        distributed.enable_compile_cache(cfg)
        assert distributed._cache_dir == str(d)


def test_claim_devices_reports_platform_and_refuses_silent_fallback(monkeypatch):
    import jax
    from jax._src import xla_bridge

    from oryx_tpu.parallel import distributed

    info = distributed.claim_devices()
    assert info == {
        "platform": "cpu",
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": len(jax.devices()),
    }
    # an accelerator that failed to initialise + no explicit platform =
    # the CPU was reached by accident
    monkeypatch.setattr(distributed, "_device_info", None)
    monkeypatch.setitem(xla_bridge._backend_errors, "tpu", "no chip")
    prev = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        import pytest

        with pytest.raises(RuntimeError, match="one owner"):
            distributed.claim_devices()
    finally:
        jax.config.update("jax_platforms", prev)
