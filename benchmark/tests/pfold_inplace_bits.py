"""The coded fold kernel, which writes p over p_prev in place, checked on
the chip:

    python benchmark/tests/pfold_inplace_bits.py [--parent <checkout>] [--reps 3]

At the plans of the 7-point Poisson operator at 192^3 and 320^3 (four
nibble code streams), one vector and K = 3 columns, on random operands:
the fold kernel's `y` must hold the bits of the plain kernel on the p it
returned (a store that raced block j+1's window would have fed that
window new p where p_prev was due), and, with `--parent`, `(y, p)` must
hold the bits of that checkout's kernel, which kept p in a buffer of its
own. Interpret mode runs a DMA at its start and cannot show a race; this
run can. Then each kernel carries p through 50 calls in one `fori_loop`,
as the CG loop does, and the least time of a call over three loops is
printed: a kernel that keeps p apart pays XLA's copy of p there. Prints
one JSON line a case and exits 1 on any difference. `--sizes 80 --cpu`
rehearses it off the chip in interpret mode. Not run by the benchmark.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

TRIPS = 50


def operands(pd, n, k, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    offsets = (-n * n, -n, -1, 0, 1, n, n * n)
    plan = pd.plan_dia_padded(offsets, n**3, 4)
    no, L = n**3, pd.LANES
    codes = np.zeros((7, plan["code_len"]), dtype=np.uint8)
    codes[:, :no] = rng.integers(0, 4, (7, no))
    packed = pd.pack_nibble_codes(codes)
    total = (plan["n_blocks"] + 3) * plan["block_rows"]
    lead = () if k is None else (k,)

    def frame():
        f = np.zeros(lead + (total * L,), dtype=np.float32)
        f[..., plan["o0"] : plan["o0"] + no] = rng.standard_normal(lead + (no,))
        return f.reshape(lead + (total, L))

    static = dict(offsets=offsets, kk=(4,) * 7, code_row=tuple(range(7)),
                  plan=plan, total_rows=total)
    arrays = (
        rng.standard_normal((7, 4)).astype(np.float32) / 7,
        np.array([no], dtype=np.int32),
        packed.reshape(packed.shape[0], -1, L),
        frame(), frame(),
        np.full(k or 1, 0.5, dtype=np.float32),
    )
    return static, arrays


def variants(parent):
    """name -> the module whose fold kernel runs"""
    from partitionedarrays_jl_tpu.ops import pallas_dia

    out = {"in_place": pallas_dia}
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_pallas_dia",
            os.path.join(parent, "partitionedarrays_jl_tpu/ops/pallas_dia.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out["parent"] = mod
    return out


def run_case(n, k, reps, parent, interpret):
    import jax
    import numpy as np

    from partitionedarrays_jl_tpu.ops import pallas_dia

    same, times = {}, {}
    for rep in range(reps):
        static, arrays = operands(pallas_dia, n, k, 1000 * n + rep)
        outs = {}
        for v, pd in variants(parent).items():
            def fold(pp, cb, no, codes, r, beta, pd=pd):
                return pd.dia_coded_padded_pallas(
                    cb, no, codes, r, interpret=interpret, pfold=(pp, beta),
                    **static,
                )

            def loop(pp, *rest, fold=fold):
                def body(_, c):
                    y, p = fold(c[1], *rest)
                    return (c[0] + y, p)

                return jax.lax.fori_loop(0, TRIPS, body, (pp * 0, pp))

            args = (arrays[4],) + arrays[:4] + arrays[5:]
            once = jax.jit(fold).lower(*args).compile()
            carried = jax.jit(loop).lower(*args).compile()
            y, p = once(*args)
            outs[v] = (np.asarray(y), np.asarray(p))
            if rep == 0:
                dev = [jax.device_put(a) for a in args]
                jax.block_until_ready(carried(*dev))
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(carried(*dev))
                    best = min(best, (time.perf_counter() - t0) / TRIPS)
                times[v] = round(best * 1e6, 2)
        y0, p0 = outs["in_place"]
        plain = pallas_dia.dia_coded_padded_pallas(
            *arrays[:3], p0, interpret=interpret, **static
        )
        outs["plain_on_p"] = (np.asarray(plain), p0)
        for v, (y, p) in outs.items():
            same.setdefault(v, []).append(
                bool(np.array_equal(y, y0) and np.array_equal(p, p0))
            )
    line = {"n": n, "k": k or 1, "reps": reps, "plan": static["plan"],
            "same_bits": same, "us_per_call": times,
            "device": jax.devices()[0].device_kind}
    print("pfold_inplace_bits:", json.dumps(line), flush=True)
    return all(all(v) for v in same.values())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default="")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--sizes", default="192,320")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse in interpret mode off the chip")
    a = ap.parse_args()
    import jax

    if jax.devices()[0].platform != "tpu" and not a.cpu:
        print("pfold_inplace_bits: needs a TPU", file=sys.stderr)
        return 2
    ok = True
    for n in (int(s) for s in a.sizes.split(",")):
        for k in (None, 3):
            ok = run_case(n, k, a.reps, a.parent, a.cpu) and ok
    print("pfold_inplace_bits: " + ("all same bits" if ok else "BITS DIFFER"),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
