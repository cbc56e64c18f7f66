"""Smoke run of the steal runtime's main path on a TPU.

    python chip_smoke.py               # one chip: kernels, DAG, DD, serving
    python chip_smoke.py --four-chips  # four chips: mesh vs vmap DAG drain

Everything runs in this one process; it starts no child.  The script
refuses to run unless JAX's first device is a TPU.  Each phase prints
``CHECK <phase> ...`` lines and raises on the first mismatch, so any
failure exits nonzero with a traceback.  The last line of a passing run
is ``{"ok": true, "device": {...}}``.

Phases (one chip):

* ``kernels`` — the ``pallas`` backend against the ``reference`` backend
  at the paper's geometry (``configs/paper_lfq.py``): push, pop_bulk,
  steal, steal_exact, window and transfer, with and without donation,
  bit-identical; the compiled op holds the Pallas kernel.
* ``dag`` — the Fig. 9 DAG (2.5M nodes) drained by ``StealRuntime.run``
  on 8 lanes with the ``auto`` backend; every node explored, the same
  count as the ``reference`` run.
* ``dd`` — ``parallel_solve`` on a seeded 18-item knapsack equals the
  dynamic-programming optimum.
* ``serve`` — ``DecodeCluster`` serves llama3.2-1b at its published
  widths with seeded random weights: the same tokens under ``reference``
  and ``auto``, and the model's own greedy ``decode_step`` tokens.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

SEED = 0
# Where the served tokens and the model's own greedy tokens differ, the
# reference's top-2 logit margin at that step must be within this many
# bf16 ulps (2**-8 relative) of the larger logit: both paths compute in
# bf16, with different batch shapes and reduction orders.
MARGIN_ULPS = 8


def check(name: str, ok: bool, detail: str) -> None:
    print(f"CHECK {name} {'ok' if ok else 'FAIL'} {detail}", flush=True)
    if not ok:
        raise AssertionError(f"{name}: {detail}")


def tpu_device() -> dict:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _same(a, b) -> bool:
    import jax
    import numpy as np

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


def phase_kernels(*, capacity: int, initial: int, pushes, proportions,
                  max_steal: int, lanes: int = 4, seed: int = SEED):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ops as bulk_ops

    pal = bulk_ops.make_ops("pallas")
    ref = bulk_ops.make_ops("reference")
    rng = np.random.default_rng(seed)

    def payload(rows):
        return {"id": np.arange(1, rows + 1, dtype=np.int32),
                "w": rng.standard_normal((rows, 8), np.float32),
                "h": rng.standard_normal((rows, 2)).astype(jnp.bfloat16)}

    ring = payload(capacity)
    lo = capacity - initial // 3           # the live window wraps the ring

    def state():
        # A fresh device copy per call: a donating op consumes its input.
        return bulk_ops.QueueState(
            buf=jax.tree_util.tree_map(jnp.asarray, ring),
            lo=jnp.int32(lo), size=jnp.int32(initial))

    def batch(rows):
        return jax.tree_util.tree_map(jnp.asarray, payload(rows))

    gathered = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x).reshape((lanes, max_steal) + x.shape[1:]),
        payload(lanes * max_steal))

    cases = []
    for b in pushes:
        bt = batch(b)
        cases.append((f"push b={b}",
                      lambda o, d, bt=bt, b=b: o.push(state(), bt, b,
                                                      donate=d)))
        cases.append((f"pop_bulk n={b}",
                      lambda o, d, b=b: o.pop_bulk(state(), b, b, donate=d)))
    for p in proportions:
        cases.append((f"steal p={p}",
                      lambda o, d, p=p: o.steal(state(), p,
                                                max_steal=max_steal,
                                                donate=d)))
    for n in (1, initial // 2, max_steal):
        cases.append((f"steal_exact n={n}",
                      lambda o, d, n=n: o.steal_exact(
                          state(), n, max_steal=max_steal, donate=d)))
    cases.append(("window", lambda o, d: o.window(
        state(), max_steal=max_steal, donate=d)))
    for row, n in ((0, max_steal), (lanes - 1, 777)):
        cases.append((f"transfer src={row} n={n}",
                      lambda o, d, row=row, n=n: o.transfer(
                          state(), gathered, row, n, max_steal=max_steal,
                          donate=d)))

    for name, run in cases:
        for donate in (False, True):
            out_p, out_r = run(pal, donate), run(ref, donate)
            check("kernels", _same(out_p, out_r),
                  f"{name} donate={donate} pallas == reference")

    # The kernel-routed ops compile to Pallas custom calls.
    q, bt = state(), batch(pushes[-1])
    programs = {
        "push": (lambda q, b: pal.push(q, b, pushes[-1]), (q, bt)),
        "pop_bulk": (lambda q: pal.pop_bulk(q, pushes[-1], pushes[-1]),
                     (q,)),
        "steal": (lambda q: pal.steal(q, 0.5, max_steal=max_steal), (q,)),
        "window": (lambda q: pal.window(q, max_steal=max_steal), (q,)),
        "transfer": (lambda q, g: pal.transfer(q, g, 1, 100,
                                               max_steal=max_steal),
                     (q, gathered)),
    }
    for name, (fn, args) in programs.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        check("kernels", "tpu_custom_call" in text,
              f"{name} compiled program holds the Pallas kernel")
    dtypes = sorted({str(x.dtype) for x in jax.tree_util.tree_leaves(ring)})
    preds = {"push": bulk_ops.kernel_push_available,
             "pop_bulk": bulk_ops.kernel_pop_available,
             "steal": bulk_ops.kernel_steal_available,
             "transfer": bulk_ops.kernel_transfer_available}
    for b in sorted(set(pushes) | {max_steal}):
        routed = {op: "kernel" if all(pred(capacity, b, d) for d in dtypes)
                  else "reference" for op, pred in preds.items()}
        print(f"routing capacity={capacity} bound={b} {dtypes}: {routed}",
              flush=True)


# ---------------------------------------------------------------------------
# DAG drain
# ---------------------------------------------------------------------------


def dag_policy(batch: int):
    from repro.core.policy import StealPolicy

    return StealPolicy(proportion=0.5, low_watermark=batch // 2,
                       high_watermark=4 * batch, max_steal=1024)


def drain_dag(rt, n_nodes: int, batch: int, fused: int,
              show_program: bool = False) -> dict:
    """Seed the root on lane 0 and drain the Fig. 9 DAG."""
    import jax
    import jax.numpy as jnp

    from benchmarks.fig9_dag import _device_body

    body = _device_body(n_nodes, batch, rt.ops)
    rt.push(0, jnp.zeros((1,), jnp.int32), 1)
    carry = jnp.zeros((rt.n_workers,), jnp.int32)
    if show_program:
        text = rt.lower(body, carry, fused=fused).compile().as_text()
        check("dag", "tpu_custom_call" in text,
              f"backend={rt.ops.name} fused program holds the queue kernels")
    t0 = time.perf_counter()
    carry = rt.run(body, carry, max_rounds=20 * n_nodes // batch + 100,
                   fused=fused)
    jax.block_until_ready(carry)
    wall = time.perf_counter() - t0
    return {"explored": int(jnp.sum(carry)), "rounds": rt.rounds_run,
            "wall_s": wall, "resolved": rt.ops.resolved, "ops": repr(rt.ops),
            "transferred": [r.n_transferred for r in rt.telemetry.rounds],
            "final_sizes": [int(x) for x in rt.sizes()]}


def phase_dag(*, n_nodes: int, lanes: int = 8, batch: int = 64,
              capacity: int = 16_384, fused: int = 8):
    import jax

    from repro.runtime import StealRuntime

    runs = {}
    for backend in ("auto", "reference"):
        rt = StealRuntime(lanes, capacity, jax.ShapeDtypeStruct(
            (), "int32"), policy=dag_policy(batch), backend=backend,
            max_pop=batch)
        r = runs[backend] = drain_dag(rt, n_nodes, batch, fused,
                                      show_program=backend == "auto")
        print(f"dag backend={backend} ops.resolved={r['resolved']} "
              f"{r['ops']} explored={r['explored']} rounds={r['rounds']} "
              f"transferred={sum(r['transferred'])} wall_s={r['wall_s']}",
              flush=True)
    check("dag", runs["auto"]["explored"] == n_nodes,
          f"auto explored {runs['auto']['explored']} of {n_nodes}")
    check("dag", runs["auto"]["explored"] == runs["reference"]["explored"],
          f"auto explored == reference explored "
          f"({runs['reference']['explored']})")


# ---------------------------------------------------------------------------
# DD solve
# ---------------------------------------------------------------------------


def phase_dd(*, n_items: int = 18, seed: int = 3):
    from repro.core.dd.knapsack import dp_solve, random_instance
    from repro.core.dd.parallel import parallel_solve

    inst = random_instance(n_items, seed=seed)
    t0 = time.perf_counter()
    opt, stats = parallel_solve(inst, n_workers=8, batch=4, explore_width=8)
    wall = time.perf_counter() - t0
    want = dp_solve(inst)
    print(f"dd backend={stats['backend']} supersteps={stats['supersteps']} "
          f"explored={stats['explored']} transferred={stats['transferred']} "
          f"wall_s={wall}", flush=True)
    check("dd", opt == want, f"parallel_solve {opt} == dp_solve {want}")


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def _requests(n: int, vocab: int, max_prompt: int, max_new: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [([int(t) for t in rng.integers(1, vocab,
                                           size=int(rng.integers(1, max_prompt
                                                                 + 1)))],
             int(rng.integers(1, max_new + 1))) for _ in range(n)]


def _greedy(step, params, cache0, prompt, max_new):
    """The model's own greedy decode: one token per ``decode_step``, no
    paging, no batching.  Returns the tokens and each step's logits."""
    import jax.numpy as jnp
    import numpy as np

    cache, toks, logits = cache0, [], []
    feed = list(prompt)
    for i in range(len(prompt) + max_new - 1):
        tok = feed[i] if i < len(feed) else toks[-1]
        out, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32))
        if i >= len(prompt) - 1:
            row = np.asarray(out[0, 0], np.float32)
            logits.append(row)
            toks.append(int(row.argmax()))
    return toks, logits


def phase_serve(*, arch: str = "llama3.2-1b", lanes: int = 4,
                n_requests: int = 8, max_prompt: int = 16, max_new: int = 16,
                capacity: int = 128, seed: int = SEED):
    import jax
    import numpy as np

    from repro import configs
    from repro.models import build_model
    from repro.serve.decode import DecodeCluster, DecodePolicy
    from repro.serve.scheduler import Request

    cfg = configs.get(arch)
    print(f"serve {arch}: n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size}", flush=True)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    policy = DecodePolicy(n_slots=4, max_prompt=max_prompt, max_new=max_new)
    data = _requests(n_requests, cfg.vocab_size, max_prompt, max_new, seed)

    served = {}
    for backend in ("reference", "auto"):
        cluster = DecodeCluster(model, params, policy=policy, n_lanes=lanes,
                                capacity=capacity, execution="vmap",
                                backend=backend)
        reqs = [Request(prompt=p, max_new=mn) for p, mn in data]
        cluster.submit(reqs)
        t0 = time.perf_counter()
        done = cluster.run_until_drained(max_steps=40 * (max_prompt
                                                         + max_new))
        wall = time.perf_counter() - t0
        check("serve", len(done) == len(reqs),
              f"backend={backend} served {len(done)} of {len(reqs)}")
        served[backend] = [r.output for r in reqs]
        st = cluster.stats()
        print(f"serve backend={backend} resolved={st['backend']} "
              f"rounds={cluster.rounds} stolen={st['stolen']} "
              f"wall_s={wall}", flush=True)
    check("serve", served["reference"] == served["auto"],
          "tokens identical under reference and auto")

    step = jax.jit(model.decode_step)
    cache0 = model.make_cache(1, max_prompt + max_new)
    for i, (prompt, mn) in enumerate(data):
        toks, logits = _greedy(step, params, cache0, prompt, mn)
        got = served["auto"][i]
        if got == toks:
            check("serve", True, f"request {i} matches greedy decode_step "
                                 f"({mn} tokens)")
            continue
        k = next(j for j in range(mn) if j >= len(got) or got[j] != toks[j])
        top2 = np.sort(logits[k])[-2:]
        margin = float(top2[1] - top2[0])
        bound = MARGIN_ULPS * 2.0 ** -8 * float(np.abs(top2).max())
        check("serve", margin <= bound,
              f"request {i} first differs at token {k}: reference top-2 "
              f"margin {margin} within {MARGIN_ULPS} bf16 ulps ({bound})")


# ---------------------------------------------------------------------------
# four chips: mesh vs vmap
# ---------------------------------------------------------------------------


def phase_four_chips(*, n_nodes: int, batch: int = 64,
                     capacity: int = 16_384, fused: int = 8):
    import jax

    from repro.distributed import launch_runtime

    n = len(jax.devices())
    check("mesh", n == 4, f"{n} devices visible")
    spec = jax.ShapeDtypeStruct((), "int32")
    runs = {}
    for execution in ("mesh", "vmap"):
        rt = launch_runtime(4, capacity, spec, execution=execution,
                            policy=dag_policy(batch), max_pop=batch)
        r = runs[execution] = drain_dag(rt, n_nodes, batch, fused)
        print(f"mesh-parity execution={execution} "
              f"ops.resolved={r['resolved']} explored={r['explored']} "
              f"rounds={r['rounds']} transferred={sum(r['transferred'])} "
              f"final_sizes={r['final_sizes']} wall_s={r['wall_s']}",
              flush=True)
    m, v = runs["mesh"], runs["vmap"]
    check("mesh", m["explored"] == v["explored"] == n_nodes,
          f"explored mesh {m['explored']} == vmap {v['explored']} "
          f"== {n_nodes}")
    check("mesh", m["transferred"] == v["transferred"],
          f"per-round transferred identical over {len(m['transferred'])} "
          f"rounds")
    check("mesh", m["final_sizes"] == v["final_sizes"],
          f"final sizes identical {m['final_sizes']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh vs vmap DAG parity")
    args = ap.parse_args(argv)
    device = tpu_device()

    from repro.configs.paper_lfq import CONFIG
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache(ROOT)}", flush=True)
    print(f"device: {json.dumps(device)}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(n_nodes=CONFIG.dag_nodes_small)
    else:
        phase_kernels(capacity=CONFIG.queue_capacity,
                      initial=CONFIG.bench_initial_size,
                      pushes=CONFIG.push_batch_sizes,
                      proportions=CONFIG.steal_proportions,
                      max_steal=CONFIG.max_steal)
        phase_dag(n_nodes=CONFIG.dag_nodes_small,
                  capacity=CONFIG.queue_capacity)
        phase_dd()
        phase_serve()
    print(f"total wall_s={time.perf_counter() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
