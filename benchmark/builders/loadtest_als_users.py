"""Builder `loadtest_als_users`: the arrays and the model of
`loadtest_als` for a catalog with more users than items, whose state on
the chip is mostly its staged user matrix (several GB).

Its first act, on import, is to resolve the program's rule that decides
whether a user matrix is staged. A program without it (the parent of the
PR that brought this cell) stages nothing past a 2 GiB constant, says so
in one log line and serves every request by the vector path, so
`staged()` would never turn true and `run.py` would raise after 600 s:
here it fails in seconds, with the reason, before anything is made."""

from __future__ import annotations

import resource

try:
    from oryx_tpu.app.als.serving_model import user_stage_budget
except ImportError as e:
    raise ImportError(
        "this program stages a user matrix only under a 2 GiB constant "
        "(oryx_tpu.app.als.serving_model has no `user_stage_budget`): the configuration's "
        "user matrix is past it and would be served by the vector path, which the cell "
        "does not measure; it cannot run on this program"
    ) from e

from benchmark.builders.loadtest_als import Built, make_arrays  # noqa: F401
from benchmark.builders.loadtest_als import build as _build
from benchmark.builders.loadtest_als import staged as _staged
from benchmark.builders.loadtest_als import warm_scan_programs as _warm


def _host_gb() -> tuple[float, float]:
    """(resident now, resident at its fullest) of this process, GB."""
    with open("/proc/self/statm", encoding="ascii") as f:
        now = int(f.read().split()[1]) * resource.getpagesize() / 1e9
    return now, resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def build(config: dict, seed: int, score_dtype: str | None = None) -> Built:
    """`loadtest_als.build`, and what the host holds before anything is staged."""
    built = _build(config, seed, score_dtype=score_dtype)
    print(
        "host resident when the builder returned: %.2f GB (fullest %.2f)" % _host_gb(),
        flush=True,
    )
    return built


def staged(model) -> bool:
    """True only when the staged matrix holds every user of the store. A
    refusal never reads as staged: it ends the run at once, with the
    program's own numbers, where waiting would end it after 600 s."""
    if model._x_stage_refused:
        from oryx_tpu.common import metrics

        raise RuntimeError(
            "the program refused to stage the user matrix (serving.users.stage.refused = 1, "
            "budget %s bytes): every request would go by the vector path, which this cell "
            "does not measure"
            % metrics.registry.gauge("serving.users.stage-budget-bytes").value
        )
    return _staged(model) and len(model._x_ids) == model.x.size()


def warm_scan_programs(model, batch_buckets, how_many: int, known_per_user: int) -> int:
    """The indexed programs of the mix's buckets, as `loadtest_als` warms
    them, after a line that says what was staged under which budget and
    what the host held at its fullest."""
    import jax

    from oryx_tpu.common import metrics

    snap = metrics.registry.snapshot()

    def gauge(name):
        return (snap.get(name) or {}).get("value")

    stage = snap.get("serving.users.stage.seconds") or {}
    print(
        "user staging: %d rows of %d users staged, %s bytes %s of a budget of %s (read now: %d), "
        "refused %s, %.2f s in %d staging(s); host resident now %.2f GB (fullest %.2f)"
        % (
            len(model._x_ids), model.x.size(), gauge("serving.users.staged-bytes"),
            tuple(model._x_matrix.shape), gauge("serving.users.stage-budget-bytes"),
            user_stage_budget(jax.local_devices()[:1]), gauge("serving.users.stage.refused"),
            stage.get("sum") or 0.0, stage.get("count") or 0,
            *_host_gb(),
        ),
        flush=True,
    )
    return _warm(model, batch_buckets, how_many, known_per_user)
