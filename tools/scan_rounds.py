"""Time the one jitted scan program of the served path,
``pallas_topn._streaming_topk_multi_indexed``, on the chip with no server
around it, at the benchmark configurations' shapes (ISSUE 25):

    python tools/scan_rounds.py --out DIR [--root CHECKOUT] [--seed 0]
    python tools/scan_rounds.py --compare DIR_A DIR_B

For each ``--shape ITEMSxFEATURES`` and each batch (8, 16 rows; ``--k`` 32;
seeded standard-normal float32 factors made on the device) it prints one
JSON line a case: ms a pass (``--passes`` back-to-back dispatches, waited
for at the end, over their count; best and median of ``--repeats``), and,
where the checkout's kernel can count (``count_rounds``), the score tiles
that passed the gate and the selection rounds run in them. Cases: b rows
that are d distinct users (d = 1 is b copies of one user; d = b is the
worst a batch of that size can be on random data), and ``sorted``: a
matrix whose scores for the query ascend with the item id, so every tile
enters k items (adversarial: no cell looks like it).

``--root`` names the checkout whose ``oryx_tpu`` is imported (default:
this file's own), so one chip call can run parent and change from the
same script; every case's (scores, ids) go to ``DIR/<case>.npz`` and
``--compare`` demands of two runs equal ids, equal counts of gated tiles
and rounds, and scores within 1e-6 of the case's score scale.
``--tiny --interpret --platform cpu`` is the CPU rehearsal: its times are
the interpreter's and mean nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import inspect
import json
import statistics
import sys
import time
from pathlib import Path


# Two checkouts may sum a score's products in another order (the tail
# plane's on the VPU, the rest on the MXU): ids, gated tiles and rounds
# must be equal, scores within this share of the case's largest score.
SCORE_TOLERANCE = 1e-6


def compare(dir_a: Path, dir_b: Path) -> int:
    import numpy as np

    names = sorted(p.name for p in dir_a.glob("*.npz"))
    if not names or names != sorted(p.name for p in dir_b.glob("*.npz")):
        print(f"scan_rounds: {dir_a} and {dir_b} do not hold the same cases")
        return 1
    bad = 0
    for name in names:
        a, b = np.load(dir_a / name), np.load(dir_b / name)
        scale = float(np.max(np.abs(a["vals"]))) or 1.0
        err = float(np.max(np.abs(a["vals"].astype(np.float64) - b["vals"]))) / scale
        counts = [key for key in ("gated_tiles", "rounds") if key in a and key in b]
        same = (
            a["vals"].dtype == b["vals"].dtype
            and np.array_equal(a["idxs"], b["idxs"])
            and err <= SCORE_TOLERANCE
            and all(int(a[key]) == int(b[key]) for key in counts)
        )
        bad += not same
        print(
            f"scan_rounds: compare {name}: {'same' if same else 'DIFFERENT'}"
            f" (score_err_of_scale {err:.3g}; compared {', '.join(['ids', 'scores'] + counts)})"
        )
    print(f"scan_rounds: compared {len(names)} cases, {bad} differ")
    return 1 if bad else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=Path)
    ap.add_argument("--root", type=Path, default=Path(__file__).resolve().parents[1])
    ap.add_argument("--shape", action="append", help="ITEMSxFEATURES, repeatable")
    ap.add_argument("--batches", default="8,16")
    ap.add_argument("--k", type=int, default=32, help="the cells' k bucket")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--passes", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--compare", nargs=2, type=Path, metavar=("DIR_A", "DIR_B"))
    args = ap.parse_args(argv)
    if args.compare:
        return compare(*args.compare)
    if args.out is None:
        ap.error("--out is required")

    sys.path.insert(0, str(args.root.resolve()))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from oryx_tpu.ops import pallas_topn as ptn
    from oryx_tpu.ops import topn as topn_ops

    dev = jax.devices()[0]
    if dev.platform != args.platform:
        print(f"scan_rounds: wanted platform {args.platform}, JAX gave {dev.platform}")
        return 2
    shapes = args.shape or (
        ["40000x50", "24000x250"] if args.tiny else ["20000000x50", "5000000x250"]
    )
    batches = [int(b) for b in args.batches.split(",")]
    can_count = "count_rounds" in inspect.signature(ptn._streaming_topk_impl).parameters
    args.out.mkdir(parents=True, exist_ok=True)
    print(
        "scan_rounds: "
        + json.dumps(
            {
                "root": str(args.root), "platform": dev.platform,
                "device_kind": dev.device_kind, "counts": can_count,
                "k": args.k, "passes": args.passes, "repeats": args.repeats, "seed": args.seed,
            }
        ),
        flush=True,
    )

    counting = _counting_program(ptn, args.k, args.interpret) if can_count else None
    for shape in shapes:
        items, features = (int(v) for v in shape.lower().split("x"))
        up = topn_ops.upload_random(items, features, jnp.float32, seed=args.seed, streaming=True)
        gen = np.random.default_rng(args.seed)
        users = gen.standard_normal((4096, features)).astype(np.float32)
        # the adversarial query: all of its score is feature 0, whose row in
        # the sorted matrix ascends with the item id
        users[0] = 0.0
        users[0, 0] = 1.0
        x_dev = jnp.asarray(users)
        def report(name, idx, is_sorted):
            line = _run_case(ptn, up, x_dev, idx, args, counting)
            case = f"{items}x{features}-{name}"
            counted = {key: line[key] for key in ("gated_tiles", "rounds") if key in line}
            np.savez(
                args.out / f"{case}.npz", vals=line.pop("vals"), idxs=line.pop("idxs"), **counted
            )
            line = {"case": case, "items": items, "features": features, "sorted": is_sorted, **line}
            print("scan_rounds: " + json.dumps(line), flush=True)

        for b in batches:
            d = 1
            while d <= b:
                # users 1..d, each b / d times over: d distinct rows in b
                report(f"b{b}-distinct{d}", [1 + j % d for j in range(b)], False)
                d *= 2
        ramp = jnp.arange(up.mat_t.shape[1], dtype=jnp.float32)
        up = dataclasses.replace(up, mat_t=_row0_setter()(up.mat_t, ramp))
        for b in batches:
            report(f"b{b}-sorted", [0] * b, True)
        del up, x_dev
    return 0


def _run_case(ptn, up, x_dev, idx, args, counting) -> dict:
    """One case: the served program's outputs, ms a pass, and the kernel's
    counts where ``counting`` (the jitted counting program) is given."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    idx_b = jnp.asarray(idx, jnp.int32)
    # a float32 handle may hold its last `features % 8` rows in a tail
    # plane (pallas_topn.tail_rows); a checkout from before has none
    tail = {} if getattr(up, "tail", None) is None else {"tail": up.tail}

    def dispatch():
        return ptn._streaming_topk_multi_indexed(
            up.mat_t, up.norms, None, None, None, x_dev, idx_b[None, :],
            k=args.k, n_items=up.n_items, cosine=False, interpret=args.interpret, **tail,
        )

    def ms_per_pass():
        t0 = time.perf_counter()
        jax.block_until_ready([dispatch() for _ in range(args.passes)])
        return (time.perf_counter() - t0) * 1e3 / args.passes

    # one array of score bits and ids since PR 41 (pallas_topn.pack_hits);
    # a checkout from before hands back the pair
    split_hits = getattr(ptn, "split_hits", lambda pair: pair)
    t0 = time.perf_counter()
    vals, idxs = split_hits(jax.block_until_ready(dispatch()))
    first_s = time.perf_counter() - t0  # holds the compile, if there was one
    ms_per_pass()  # warm
    per_pass = [ms_per_pass() for _ in range(args.repeats)]
    line = {
        "b": len(idx), "distinct_rows": len(set(idx)),
        "ms_per_pass_best": min(per_pass), "ms_per_pass_median": statistics.median(per_pass),
        "first_call_s": first_s, "tiles": -(-up.n_items // ptn.SCORE_TILE),
        "vals": np.asarray(vals[0]), "idxs": np.asarray(idxs[0]),
    }
    if counting is not None:
        *_, counts = counting(
            up.mat_t, up.norms, None, None, None, x_dev[idx_b], n_items=up.n_items, **tail
        )
        line["gated_tiles"], line["rounds"] = (int(c) for c in np.asarray(counts)[0])
    return line


@functools.lru_cache(maxsize=None)
def _counting_program(ptn, k: int, interpret: bool):
    import jax

    return jax.jit(
        functools.partial(
            ptn._streaming_topk_impl, k=k, cosine=False, interpret=interpret, count_rounds=True
        ),
        static_argnames=("n_items",),
    )


@functools.lru_cache(maxsize=None)
def _row0_setter():
    import jax

    return jax.jit(lambda m, r: m.at[0].set(r), donate_argnums=0)


if __name__ == "__main__":
    sys.exit(main())
