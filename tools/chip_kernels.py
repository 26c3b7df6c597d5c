"""Kernel phase of chip_smoke.py: compile and run every device kernel at
the shapes the benchmark cells will use, each against a plain XLA float32
``precision=HIGHEST`` reference computed on the same device.

    python tools/chip_kernels.py --out DIR [--seed 0] [--tiny --interpret]

Runs as its own process (it owns the chip while it runs) and fails if
JAX did not give it the platform ``--platform`` names. Every check runs
even after one fails, so one chip call lists every refusal; the process
exits non-zero if any check failed. ``--tiny --interpret`` is the CPU
rehearsal: same code, small shapes, Pallas under the interpreter.

What is checked (ISSUE 21 item 5):

- the streaming top-k scan (ops/pallas_topn.py, the one kernel
  ``oryx_topn_scan``) at 250 and 50 features x >= 1M items: f32 / bf16 /
  int8 items, dot / cosine, one scan group (256 rows) and two (512 rows
  cut into groups of 256), query vectors given flat (``single``), as
  groups (``multi``: lax.map over pallas_call) and as rows of a staged
  query matrix (``multi-indexed``): not the full cross, see
  ``scan_checks`` for which and why;
- the same kernel at a k bucket of 256 (two vregs of state a row);
- the mesh-sharded scan on whatever devices exist, at both widths (f32,
  so the split layout: a main and a tail plane on every shard; the int8
  planes shard the same way and are summed in full there);
- the fused Pallas k-means sweep, full and mini-batch, at d = 250 with k
  at the ``fits_vmem`` edge;
- the IVF device probe at >= 1M items;
- the device fold-in program against the float64 host fold;
- the Gram pass over the uploaded item matrix (ops/gram.py ``oryx_gram``,
  the anonymous fold-in's ``YtY``) against float64 NumPy of the rows the
  handle holds: the streaming layout in f32 (both widths) and bf16, and
  the mesh-sharded layout on whatever devices exist, each to ``GRAM_TOL``
  of the largest entry.

Tolerances. A result row is (ids, scores). Against exact f32 scores S of
the SAME original matrix, a check demands (a) every returned score within
``tol`` of S[id]; (b) every returned id a legitimate member of the top k:
fewer than k items score more than S[id] + ``tol``; (c) ids distinct and
real. ``tol`` is a fraction of the row's Cauchy-Schwarz scale
|q| * max|y| (1 for cosine):

- f32 items: 1e-5. The kernel accumulates in f32 at HIGHEST precision; the
  only difference from the reference is summation order over <= 250 terms.
- bf16 items: 1e-2. Items and queries round to 8 mantissa bits (relative
  2^-9 each), so each product is off by <= 2^-8 of itself and the dot by
  <= 2^-8 * |q||y| in the worst case; 1e-2 leaves 2.5x headroom.
- int8 items: 2.5e-4, with queries that bfloat16 holds exactly. Two
  planes of row-quantized codes carry ~14 bits: an element is off by <=
  absmax/(127*254*2), the dot by <= 2.5e-4 * |q||y| at f = 250 (less at
  50). On the TPU the first plane's dot takes one bf16 MXU pass
  (``_dot_precision_for``: DEFAULT), which rounds the QUERY to 8 mantissa
  bits; against arbitrary f32 queries that alone is 3.3e-4 of scale at
  250 features and 8.5e-4 at 50 (measured on a v5e, PR 21), which would
  hide a scan that lost its residual plane (1.6e-3 / 2.7e-3; the two
  planes together: 7e-6 / 9e-6, NumPy model of the same data). So the
  int8 checks round their queries to bfloat16 first: the MXU pass then
  rounds nothing, the int8 codes are exact in bf16 anyway, and what is
  left is the quantization the bound is about (measured on a v5e, PR 21:
  5.4e-6 to 6.7e-6 at 250 features, 1.2e-5 at 50). The first plane alone
  ranks the scan and keeps 4k candidates, so a true top-k item can in
  principle be dropped before the rescore: ``min_recall`` 0.99 is the
  engine's documented contract (docs/serving-scan.md), over a check's rows.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOL = {"float32": 1e-5, "bfloat16": 1e-2, "int8": 2.5e-4}
MIN_RECALL = {"float32": 1.0, "bfloat16": 1.0, "int8": 0.99}
# largest |YtY - float64 reference| over the largest entry: what the fold-in's
# scores can bear under the benchmark's 1e-5 of scale (docs/serving-scan.md);
# bfloat16 against the rows as the format holds them, with the room the CPU
# rehearsal needs (its dot of converted operands reads 1.1e-6 over one block)
GRAM_TOL = {"float32": 1e-6, "bfloat16": 1e-5}


class CheckFailed(Exception):
    pass


def expect(ok, *why) -> None:
    """A check's condition (not `assert`: that vanishes under -O)."""
    if not ok:
        raise CheckFailed(" ".join(str(w) for w in why))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--platform", default="tpu", help="platform JAX must report")
    ap.add_argument("--tiny", action="store_true", help="rehearsal shapes")
    ap.add_argument("--interpret", action="store_true", help="Pallas interpreter")
    ap.add_argument("--only", default=None, help="comma list of check-name prefixes")
    ap.add_argument("--check-timeout", type=float, default=240.0,
                    help="seconds one check may take before the process gives up")
    args = ap.parse_args()

    from oryx_tpu.parallel.distributed import claim_devices, enable_compile_cache

    device = claim_devices()
    if device["platform"] != args.platform:
        print(f"kernels: expected platform {args.platform}, got {device}", flush=True)
        return 2
    enable_compile_cache()
    if args.platform == "tpu" and args.interpret:
        print("kernels: --interpret on the chip would prove nothing", flush=True)
        return 2

    checks = Checks(args)
    checks.run_all()
    result = {
        "device": device,
        "interpret": args.interpret,
        "checks": checks.rows,
        "observations": checks.observations,
        "compile_seconds": round(checks.compile_seconds(), 2),
        "failed": [r["name"] for r in checks.rows if not r["ok"]],
    }
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "kernels.json"), "w", encoding="utf-8") as f:
        json.dump(result, f, indent=1)
    print(
        f"kernels: {len(checks.rows) - len(result['failed'])}/{len(checks.rows)} ok, "
        f"compile {result['compile_seconds']}s, failed: {result['failed']}",
        flush=True,
    )
    return 1 if result["failed"] else 0


class Checks:
    def __init__(self, args) -> None:
        self.args = args
        self.interpret = bool(args.interpret)
        self.rows: list[dict] = []
        self.observations: dict = {}
        self.gen = np.random.default_rng(args.seed)
        self.only = args.only.split(",") if args.only else None
        self.kernel_compiled_at: float | None = None
        # every scan variant scans at least this many items
        self.n_items = 20_000 if args.tiny else 1_000_000

    def compile_seconds(self) -> float:
        from oryx_tpu.common import metrics

        return metrics.registry.histogram("jax.compile.seconds").snapshot().get("sum", 0.0)

    def check(self, name: str, fn) -> None:
        """Run one check; a raise is a failed check, not the end of the
        run. ``fn`` returns a dict of what it measured."""
        if self.only and not any(name.startswith(p) for p in self.only):
            return
        c0 = self.compile_seconds()
        t0 = time.perf_counter()
        row = {"name": name, "ok": False}
        # a compile that never returns cannot be interrupted from Python:
        # the watchdog ends the process (and the phase) instead of letting
        # it sit on the chip until someone kills it
        watchdog = threading.Timer(self.args.check_timeout, self.give_up, (name,))
        watchdog.daemon = True
        watchdog.start()
        try:
            row.update(fn() or {})
            row["ok"] = True
        except Exception as e:  # noqa: BLE001 - recorded, and the run exits non-zero
            row["error"] = f"{type(e).__name__}: {e}"[:3000]
            row["trace"] = traceback.format_exc()[-1500:]
        finally:
            watchdog.cancel()
        row["wall_s"] = round(time.perf_counter() - t0, 2)
        row["compile_s"] = round(self.compile_seconds() - c0, 2)
        if self.kernel_compiled_at is not None:
            row["kernel_compile_s"] = round(self.kernel_compiled_at - c0, 2)
            self.kernel_compiled_at = None
        self.rows.append(row)
        print(
            f"kernels[{name}]: {'ok' if row['ok'] else 'FAILED'} "
            f"wall {row['wall_s']}s compile {row['compile_s']}s "
            + (row.get("error", "")[:1500] if not row["ok"] else json.dumps(
                {k: v for k, v in row.items() if k not in ("name", "ok", "wall_s", "compile_s")}
            )),
            flush=True,
        )

    def give_up(self, name: str) -> None:
        print(
            f"kernels[{name}]: FAILED no result after {self.args.check_timeout:.0f}s "
            "(compile or run hung); giving up the whole phase",
            flush=True,
        )
        os._exit(3)

    def run_all(self) -> None:
        for features in (250, 50):
            self.scan_checks(features)
        self.kmeans_checks()
        self.ivf_checks()
        self.fold_check()
        for features in (250, 50):
            self.gram_checks(features)

    # -- streaming scan ------------------------------------------------------

    def scan_checks(self, features: int) -> None:
        import jax.numpy as jnp

        from oryx_tpu.ops import pallas_topn as pt
        from oryx_tpu.ops import topn as topn_ops

        n = self.n_items
        k = 16  # what the serving batcher asks for a howMany=10 request
        mat = self.gen.standard_normal((n, features), dtype=np.float32)
        mat_dev = jnp.asarray(mat)
        norms_dev = jnp.linalg.norm(mat_dev, axis=1)
        n_users = 4096
        x = self.gen.standard_normal((n_users, features), dtype=np.float32)
        # int8 checks: the same queries rounded to what bfloat16 holds (TOL)
        x8 = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
        x_dev = topn_ops.upload_queries(x)
        queries = {"float32": (x, x_dev), "bfloat16": (x, x_dev),
                   "int8": (x8, topn_ops.upload_queries(x8))}
        jdt = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "int8": jnp.int8}
        small_b, big_b = (8, 264) if self.args.tiny else (256, 512)
        # Which variants. A cold Mosaic compile of one scan program takes
        # 5-17 s on a v5e (PR 21: 31 variants, 310 s), so the full cross of
        # dtype x metric x form x dispatch x width (72 programs) does not fit
        # the smoke's time limit. Kept: at 250 features every dtype at both
        # row counts and both metrics, every dtype with explicit groups (multi
        # or multi-indexed), and two groups a half under lax.map once; at 50
        # features, where only tile sizing and the int8 sublane padding
        # differ, every dtype and both row counts. float32 is stored split
        # at both widths (main plane + 2-row tail plane): dot, cosine and
        # the sharded form run it at each. PERF.md section 7 lists what is
        # left out.
        plan = {
            250: [
                ("float32", False, "one-group", "single"),
                ("float32", True, "two-groups", "single"),
                ("float32", False, "one-group", "multi"),
                ("bfloat16", False, "two-groups", "single"),
                ("bfloat16", True, "one-group", "single"),
                ("bfloat16", False, "one-group", "multi-indexed"),
                ("int8", False, "one-group", "single"),
                ("int8", False, "two-groups", "single"),
                ("int8", True, "one-group", "single"),
                ("int8", False, "one-group", "multi"),
                ("int8", False, "one-group", "multi-indexed"),
                ("int8", True, "two-groups", "multi"),
                ("int8", False, "k256", "single"),
            ],
            50: [
                ("float32", False, "two-groups", "single"),
                ("float32", True, "one-group", "single"),
                ("bfloat16", False, "one-group", "single"),
                ("int8", False, "one-group", "single"),
                ("int8", False, "two-groups", "single"),
                ("int8", True, "one-group", "multi-indexed"),
            ],
        }[features]

        def verify(dtype, cosine, rows_q, idx, vals, kk=k):
            self.kernel_compiled_at = self.compile_seconds()  # the rest is the reference's
            return verify_topk(
                mat_dev, norms_dev, jnp.asarray(rows_q), np.asarray(idx),
                np.asarray(vals, dtype=np.float32), kk, cosine,
                TOL[dtype], MIN_RECALL[dtype],
            )

        handles: dict = {}

        def handle(dtype):
            if dtype not in handles:
                handles.clear()  # one item matrix on the device at a time
                handles[dtype] = pt.upload_streaming(mat, dtype=jdt[dtype])
            return handles[dtype]

        def split_layout(up) -> dict:
            """float32 at 50 and 250 features is stored split (main plane
            of whole sublane tiles + a 2-row tail): every float32 check
            below runs that layout, and says so."""
            stored = pt.stored_feature_rows(up)
            expect(up.tail is not None and up.tail.shape[0] == features % 8, "no tail plane")
            expect(stored == features, f"{stored} feature rows stored for {features}")
            return {"feature_rows": [features, stored]}

        def run(dtype, cosine, form, dispatch):
            x, x_dev = queries[dtype]
            b = big_b if form == "two-groups" else small_b
            if form == "k256":
                kk = 256  # what the batcher asks for a howMany of 129 to 256
                q = x[:8]
                vals, idx = pt.top_k_streaming_device(
                    handle(dtype), q, kk, cosine=cosine, interpret=self.interpret
                )
                return verify(dtype, cosine, q, idx, vals, kk)
            if dispatch == "single":
                q = x[:b]
                vals, idx = pt.top_k_streaming_device(
                    handle(dtype), q, k, cosine=cosine, interpret=self.interpret
                )
            elif dispatch == "multi":
                q = x[: 2 * b]
                vals, idx = pt.split_hits(pt.scan_groups(
                    handle(dtype), q.reshape(-1, small_b, features), k,
                    cosine=cosine, interpret=self.interpret,
                ))
            else:
                rows = self.gen.integers(0, n_users, (2 * b // small_b, small_b)).astype(np.int32)
                q = x[rows.reshape(-1)]
                vals, idx = pt.split_hits(pt.scan_groups(
                    handle(dtype), rows, k, cosine=cosine,
                    interpret=self.interpret, x_dev=x_dev,
                ))
            idx, vals = np.asarray(idx).reshape(-1, k), np.asarray(vals).reshape(-1, k)
            if len(q) > big_b:  # the reference holds a [rows, n_items] f32 block
                q, idx, vals = q[::2], idx[::2], vals[::2]
            layout = split_layout(handle(dtype)) if dtype == "float32" else {}
            return {**verify(dtype, cosine, q, idx, vals), **layout}

        for dtype, cosine, form, dispatch in plan:
            self.check(
                f"scan/{features}f/{dtype}/{'cosine' if cosine else 'dot'}/{form}/{dispatch}",
                lambda v=(dtype, cosine, form, dispatch): run(*v),
            )
        handles.clear()

        from oryx_tpu.parallel.mesh import get_mesh

        def sharded():
            # the served shapes: vector submit, then the same rows by
            # index into the user matrix staged on every device, then
            # a row updated on each shard (it lands in both planes)
            mesh = get_mesh()
            up = topn_ops.upload_sharded(mat, mesh, dtype=jnp.float32)
            shards = [(s.device.id, tuple(s.data.shape)) for s in up.mat_t.addressable_shards]
            q = x[:64]
            idx, vals = topn_ops.top_k_scores_batch(up, q, k)
            handle = topn_ops.submit_top_k_multi_indexed(
                up, topn_ops.upload_queries(x, mesh=mesh), np.arange(64, dtype=np.int32), k
            )
            expect(handle.packed, "a float32 pass came back as two arrays")
            by_row, _ = handle.result()
            expect(np.array_equal(idx, by_row), "indexed submit differs from vector submit")
            last = np.asarray(up.starts) + np.asarray(up.counts) - 1
            best = 50.0 * x[: len(last)]
            top, _ = topn_ops.top_k_scores_batch(topn_ops.update_rows(up, last, best), best, 1)
            expect(top[:, 0].tolist() == last.tolist(), "a row update missed its shard")
            return {**verify("float32", False, q, idx, vals), "shards": shards,
                    "layout": topn_ops.sharded_layout(up), **split_layout(up)}

        self.check(f"scan/{features}f/float32/dot/sharded", sharded)

    # -- k-means -------------------------------------------------------------

    def kmeans_checks(self) -> None:
        import jax
        import jax.numpy as jnp

        from oryx_tpu.ops import pallas_kmeans as pk

        d = 250
        k = 512
        expect(pk.fits_vmem(k, d) and not pk.fits_vmem(k + 8, d), "fits_vmem edge moved")
        n = 8_192 if self.args.tiny else 200_000
        centers_true = 3.0 * self.gen.standard_normal((k, d), dtype=np.float32)
        pts = centers_true[self.gen.integers(0, k, n)] + self.gen.standard_normal(
            (n, d), dtype=np.float32
        )
        c0 = pts[self.gen.choice(n, k, replace=False)].copy()
        pts_dev = jnp.asarray(pts)
        hi = jax.lax.Precision.HIGHEST

        @jax.jit
        def assign(points, ctr):
            d2 = (
                jnp.sum(points * points, axis=1, keepdims=True)
                - 2.0 * jnp.dot(points, ctr.T, precision=hi)
                + jnp.sum(ctr * ctr, axis=1)[None, :]
            )
            a = jnp.argmin(d2, axis=1)
            return a, jnp.sum(jnp.maximum(jnp.min(d2, axis=1), 0.0))

        @jax.jit
        def lloyd_step(points, ctr):
            a, _ = assign(points, ctr)
            onehot = jax.nn.one_hot(a, ctr.shape[0], dtype=jnp.float32)
            sums = jnp.dot(onehot.T, points, precision=hi)
            counts = jnp.sum(onehot, axis=0)
            return jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1.0)[:, None], ctr)

        def counts_and_cost(ctr):
            a, cost = assign(pts_dev, jnp.asarray(ctr))
            return np.bincount(np.asarray(a), minlength=k), float(cost)

        iters = 3

        def full():
            ctr, counts, cost = pk.lloyd_pallas(pts, c0, iters, interpret=self.interpret)
            ref = jnp.asarray(c0)
            for _ in range(iters):
                ref = lloyd_step(pts_dev, ref)
            ref_counts, ref_cost = counts_and_cost(np.asarray(ref))
            # a point equidistant (to f32 rounding) from two centres may go
            # either way, which moves both centres by 1/count of a point
            # spacing: compare cost tightly, centres and counts loosely
            center_err = float(np.max(np.abs(np.asarray(ref) - ctr)))
            moved = int(np.sum(np.abs(ref_counts - counts)))
            expect(abs(cost - ref_cost) <= 1e-4 * ref_cost, (cost, ref_cost))
            expect(moved <= max(2, n // 10_000), (moved, "points assigned differently"))
            expect(center_err <= 0.05, center_err)
            return {"cost": cost, "ref_cost": ref_cost, "center_err": center_err, "moved": moved}

        def minibatch():
            batch = 2048 if self.args.tiny else 32_768
            _, cost0 = counts_and_cost(c0)
            ctr, counts, cost = pk.minibatch_lloyd_pallas(
                pts, c0, 4, batch, jax.random.PRNGKey(self.args.seed),
                interpret=self.interpret,
            )
            # the schedule samples inside the program; what can be checked
            # exactly is its final full-data sweep over the centres it
            # returns, and that the schedule improved on its start
            ref_counts, ref_cost = counts_and_cost(ctr)
            moved = int(np.sum(np.abs(ref_counts - counts)))
            expect(np.isfinite(ctr).all(), "non-finite centres")
            expect(abs(cost - ref_cost) <= 1e-4 * ref_cost, (cost, ref_cost))
            expect(moved <= max(2, n // 10_000), moved)
            expect(cost < cost0, (cost, cost0))
            return {"cost": cost, "ref_cost": ref_cost, "start_cost": cost0, "moved": moved}

        self.check(f"kmeans/d{d}/k{k}/full", full)
        self.check(f"kmeans/d{d}/k{k}/minibatch", minibatch)

    # -- IVF device probe ----------------------------------------------------

    def ivf_checks(self) -> None:
        import jax.numpy as jnp

        from oryx_tpu.ops import ivf as ivf_ops

        features = 250
        n = self.n_items
        k = 16
        # the device probe is what a chip runs; the CPU rehearsal forces it
        # too (auto would pick the host stage-1 path there)
        ivf_ops.configure_ann(
            enabled=True, host_stage1=False, nprobe=8 if self.args.tiny else None
        )
        n_centers = 64 if self.args.tiny else 1000
        centers = self.gen.standard_normal((n_centers, features), dtype=np.float32)
        mat = centers[self.gen.integers(0, n_centers, n)] + 0.3 * self.gen.standard_normal(
            (n, features), dtype=np.float32
        )
        queries = mat[self.gen.choice(n, 64, replace=False)] + 0.1 * self.gen.standard_normal(
            (64, features), dtype=np.float32
        )
        holder: dict = {}

        def build():
            expect(ivf_ops.ann_active(n) or self.args.tiny, "ANN tier inactive at this size")
            holder["index"] = ivf_ops.build_ivf(mat, seed=self.args.seed)
            idx = holder["index"]
            expect(idx.host_plane is None, "host stage-1 plane built: not the device probe")
            return {"cells": idx.n_cells, "slots": idx.n_slots, "nprobe": idx.resolve_nprobe()}

        def probe(cosine: bool):
            index = holder["index"]
            expect(index.resolve_nprobe() < index.n_cells, "not the probed program")
            vals, ids = ivf_ops.top_k_device(index, queries, k, cosine=cosine)
            mat_dev = jnp.asarray(mat)
            # approximate by design: what must hold exactly is that every
            # (id, score) returned is that item's true score; recall against
            # the exact scan is the index's quality on clustered data
            out = verify_topk(
                mat_dev, jnp.linalg.norm(mat_dev, axis=1), jnp.asarray(queries),
                np.asarray(ids), np.asarray(vals, dtype=np.float32), k, cosine,
                TOL["int8"], min_recall=0.9, rank_exact=False,
            )
            return out

        self.check(f"ivf/{features}f/build", build)
        if "index" in holder:
            self.check(f"ivf/{features}f/probe/dot", lambda: probe(False))
            self.check(f"ivf/{features}f/probe/cosine", lambda: probe(True))

        # ROADMAP Design 1: tests/ops/test_ivf_scan.py::test_empty_cells_are_harmless
        # fails on the CPU host path. Same scenario on the device probe,
        # reported, not judged here.
        def empty_cells():
            gen = np.random.default_rng(11)
            f = 16
            blob_a = gen.standard_normal(f).astype(np.float32)
            blob_b = gen.standard_normal(f).astype(np.float32)
            m = np.concatenate([np.tile(blob_a, (1500, 1)), np.tile(blob_b, (1500, 1))])
            index = ivf_ops.build_ivf(m.astype(np.float32), n_cells=32, seed=4)
            q = np.stack([blob_a, blob_b]).astype(np.float32)
            vals, ids = ivf_ops.top_k_device(index, q, 10, nprobe=8)
            ids = np.asarray(ids)
            ref = q @ m.T
            hits = sum(
                int(np.sum(ref[r][ids[r][ids[r] >= 0]] >= np.partition(ref[r], -10)[-10] - 1e-4))
                for r in range(2)
            )
            self.observations["ivf_empty_cells_device_probe"] = {
                "recall_at_10": hits / 20,
                "empty_cells": int((index.chunk_count_host == 0).sum()),
                "ids_query_a": ids[0].tolist(),
                "ids_query_b": ids[1].tolist(),
            }
            return self.observations["ivf_empty_cells_device_probe"]

        self.check("ivf/empty-cells-observation", empty_cells)

    # -- device fold-in ------------------------------------------------------

    def fold_check(self) -> None:
        from oryx_tpu.ops import als as als_ops

        kf = 250
        n = 2_048 if self.args.tiny else 20_000
        rows = 4 * kf

        def fold():
            y = self.gen.standard_normal((rows, kf)) * 0.3
            x = self.gen.standard_normal((rows, kf)) * 0.3
            yty, xtx = y.T @ y, x.T @ x
            xu = x[self.gen.integers(0, rows, n)].astype(np.float32)
            yi = y[self.gen.integers(0, rows, n)].astype(np.float32)
            xu_valid = self.gen.random(n) > 0.1
            yi_valid = self.gen.random(n) > 0.1
            xu[~xu_valid] = 0
            yi[~yi_valid] = 0
            values = (1.0 + 4.0 * self.gen.random(n)).astype(np.float32)
            dev = als_ops.fold_in_batch(
                yty, xtx, xu, xu_valid, yi, yi_valid, values, True, backend="device"
            )
            host = als_ops.fold_in_batch(
                yty, xtx, xu, xu_valid, yi, yi_valid, values, True, backend="host"
            )
            return compare_folds(dev, host)

        self.check(f"fold-in/{kf}f/device-vs-host", fold)


    # -- Gram matrix of the item matrix -----------------------------------------

    def gram_checks(self, features: int) -> None:
        import jax.numpy as jnp

        from oryx_tpu.ops import gram as gram_ops
        from oryx_tpu.ops import pallas_topn as pt
        from oryx_tpu.ops import topn as topn_ops
        from oryx_tpu.parallel.mesh import get_mesh

        prefix = f"gram/{features}f"
        if self.only and not any(
            prefix.startswith(p) or p.startswith(prefix) for p in self.only
        ):
            return  # no matrix drawn for checks that will not run
        # not a whole number of blocks, on one chip or a shard: the last
        # block of each is part padding, which the pass has to leave out
        n = self.n_items + 1234
        mat = self.gen.standard_normal((n, features), dtype=np.float32)

        def run(upload, rows, tol=GRAM_TOL["float32"]):
            up = upload()
            expect(gram_ops.supported(up), "a handle the Gram pass refuses")
            got = gram_ops.gram(up)  # compiles
            t0 = time.perf_counter()
            again = gram_ops.gram(up)  # the pass, the partials' download, their float64 sum
            pass_s = time.perf_counter() - t0
            want = float64_gram(rows)
            err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
            expect(np.array_equal(got, again), "two passes over one handle differ")
            expect(err < tol, f"{err:.3g} of the largest entry >= {tol}")
            return {"err_of_largest": err, "pass_s": round(pass_s, 4), **gram_ops.pass_stats(up)}

        self.check(f"{prefix}/float32", lambda: run(lambda: pt.upload_streaming(mat), mat))
        if features == 250:
            # against the rows as bfloat16 holds them: the format's rounding
            # of a row is the handle's, not the pass's
            held = np.asarray(jnp.asarray(mat).astype(jnp.bfloat16).astype(jnp.float32))
            self.check(
                f"{prefix}/bfloat16",
                lambda: run(
                    lambda: pt.upload_streaming(mat, dtype=jnp.bfloat16), held, GRAM_TOL["bfloat16"]
                ),
            )

        def sharded():
            mesh = get_mesh()
            row = run(lambda: topn_ops.upload_sharded(mat, mesh, dtype=jnp.float32), mat)
            return {**row, "devices": int(mesh.devices.size)}

        self.check(f"{prefix}/float32/sharded", sharded)


def float64_gram(rows: np.ndarray, block: int = 1 << 16) -> np.ndarray:
    """``rows^T rows`` with every product and sum in float64, a block of
    rows at a time (the whole matrix in float64 would be twice its size)."""
    total = np.zeros((rows.shape[1], rows.shape[1]), dtype=np.float64)
    for lo in range(0, rows.shape[0], block):
        part = rows[lo : lo + block].astype(np.float64)
        total += part.T @ part
    return total


def compare_folds(dev, host) -> dict:
    """Device fold (f32 Cholesky solve) against the host fold (f64
    Cholesky, f32 vectors). Same updated flags; vectors within 1e-3 of the
    largest component: the solve is conditioned like the Gramian (cond
    ~1e2-1e3 for factor matrices), so f32's 6e-8 relative rounding grows to
    <= 1e-4 relative in the solved delta; 1e-3 leaves an order of
    magnitude and is still far below what moves a ranking."""
    new_xu_d, x_upd_d, new_yi_d, y_upd_d = dev
    new_xu_h, x_upd_h, new_yi_h, y_upd_h = host
    expect(np.isfinite(new_xu_d).all() and np.isfinite(new_yi_d).all(), "non-finite fold")
    expect((x_upd_d == x_upd_h).all() and (y_upd_d == y_upd_h).all(), "updated flags differ")
    scale = max(float(np.abs(new_xu_h).max()), float(np.abs(new_yi_h).max()), 1e-12)
    err = max(
        float(np.abs(new_xu_d - new_xu_h).max()), float(np.abs(new_yi_d - new_yi_h).max())
    )
    expect(err <= 1e-3 * scale, (err, scale))
    return {"max_err": err, "scale": scale, "updated": int(x_upd_d.sum() + y_upd_d.sum())}


@functools.lru_cache(maxsize=None)
def _reference(k: int, cosine: bool):
    """The plain reference: all scores in f32 at HIGHEST precision; for each
    id under test its exact score and how many items beat it by more than
    the tolerance (a comparison count: ``lax.top_k`` over a million-wide
    row takes XLA:TPU tens of seconds to compile)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reference(mat, norms, q, ids, tol):
        s = jnp.dot(q, mat.T, precision=jax.lax.Precision.HIGHEST)
        qn = jnp.linalg.norm(q, axis=1, keepdims=True)
        if cosine:
            s = s / jnp.maximum(norms[None, :] * qn, 1e-12)
            scale = jnp.ones_like(qn)
        else:
            scale = qn * jnp.max(norms)
        got = jnp.take_along_axis(s, ids, axis=1)

        def beaten_by(col):  # one id per row at a time: [b, n] compares
            return jnp.sum(s > (col + tol * scale[:, 0])[:, None], axis=1)

        return got, jax.lax.map(beaten_by, got.T).T, scale

    return reference


def verify_topk(
    mat_dev, norms_dev, q_dev, idx, vals, k, cosine, tol, min_recall, rank_exact=True
) -> dict:
    """Judge (idx, vals) [b, k] against exact f32 scores of ``mat_dev``
    (see the module docstring). ``rank_exact=False`` (approximate index)
    keeps the value check and the recall floor but lets a returned id
    rank below the exact k-th."""
    import jax.numpy as jnp

    n = mat_dev.shape[0]
    b = idx.shape[0]
    expect(idx.shape == (b, k) and vals.shape == (b, k), (idx.shape, vals.shape))
    expect(np.isfinite(vals).all(), "non-finite scores")
    expect(((idx >= 0) & (idx < n)).all(), "ids out of range")
    expect(all(len(set(r.tolist())) == k for r in idx), "duplicate ids in a row")
    got, beaten, scale = (
        np.asarray(a)
        for a in _reference(k, bool(cosine))(
            mat_dev, norms_dev, q_dev, jnp.asarray(idx), jnp.float32(tol)
        )
    )
    value_err = float(np.max(np.abs(got - vals) / scale))
    recall = float(np.mean(beaten < k))
    expect(value_err <= tol, f"score error {value_err:.3g} of scale > {tol}")
    expect(recall >= min_recall, f"recall {recall:.4f} < {min_recall}")
    if rank_exact and min_recall >= 1.0:
        expect(int(beaten.max()) < k, f"an id is beaten by {int(beaten.max())} items (k = {k})")
    return {"value_err": value_err, "worst_rank": int(beaten.max()), "recall": recall, "rows": b}


if __name__ == "__main__":
    sys.exit(main())
