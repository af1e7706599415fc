#!/usr/bin/env python
"""Price the attention family through the real train step, per seq length.

For each sequence length, the SAME tiny TransformerLM fit runs once per
attention impl — ``full`` (materialized (L, L) scores), ``blockwise``
(flash-style linear-memory Pallas kernel), ``ring`` and ``ulysses``
(seq-sharded over the runtime mesh) — each arm AOT-dispatched through
the compile spine (``precompile_call`` + ``ShapeGuard``, zero
``compile/recompile`` / ``compile/aot_fallback`` required) and profiled
(``device_time_report``), so every variant gets an honest ``step_time``
+ ``device_time`` block from the step it would actually run in, not an
isolated-op microbench.

The measured medians then go through ``ops.ledger.price_attention``:
the fastest variant an unsharded ``attn_impl="auto"`` can legally take
(ring/ulysses need a seq-sharded mesh, so they are recorded but
excluded) becomes the persisted ``choice`` verdict for that seq-length
shape class — the record's ``auto_choice`` re-reads it through
``attention_choice`` exactly like ``models.transformer`` does, closing
the loop this bench exists for: ``attn_impl="auto"`` dispatches on
measurement, ``_BLOCKWISE_AUTO_LEN`` is only the unmeasured fallback.

On a non-TPU host the mesh is 8 simulated CPU devices and the blockwise
kernel runs in interpret mode (the only way the kernel code runs here);
on the TPU host the same ladder prices real Mosaic (on chip: not
measured).

``--standalone B,L,H,D[,Dv]`` times the op alone instead, on one
device, no mesh and no ledger: causal bf16 attention at that shape
through the flash kernels, the scan schedule and ``attention_reference``,
forward and forward + backward under one ``jax.grad``, with each
compiled program's temporaries — the figures that size moving a shape
onto the kernels (PERF.md section 7, ROADMAP S3).

Usage: python benchmarks/bench_attention.py [--seqs 256,512] [--json]
       TPUFRAME_KERNEL_LEDGER_DIR=... python benchmarks/bench_attention.py  # persist
       python benchmarks/bench_attention.py --standalone 4,1024,16,64
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir))

# heads must divide the mesh seq axis (8) for the ulysses all-to-all
VOCAB, LAYERS, HEADS, HEAD_DIM, BATCH = 64, 1, 8, 8, 2
VARIANTS = ("full", "blockwise", "ring", "ulysses")


def make_fit(seq: int, impl: str, max_len: int):
    """(mk_state, toks) for one (seq length, attn impl) arm — identical
    init seeds and token streams across impls, so arms differ only in
    the attention path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tpuframe.core.runtime import current_runtime
    from tpuframe.models import TransformerLM
    from tpuframe.train import create_train_state

    model = TransformerLM(
        vocab_size=VOCAB, num_layers=LAYERS, num_heads=HEADS,
        head_dim=HEAD_DIM, max_len=max_len, attn_impl=impl,
    )
    # state and batches live replicated on the WHOLE mesh: the sharded
    # arms (and the fused LN) shard_map over all devices, and a pytree
    # committed to device 0 would refuse to enter that program
    repl = NamedSharding(current_runtime().mesh, P())
    rng = np.random.default_rng(0)
    toks = [
        jax.device_put(
            jnp.asarray(rng.integers(0, VOCAB, (BATCH, seq)).astype(np.int32)),
            repl)
        for _ in range(16)
    ]

    def mk_state():
        state = create_train_state(
            model, jax.random.PRNGKey(0), toks[0][:1], optax.adamw(1e-3))
        return jax.device_put(state, repl)

    return mk_state, toks


def standalone(shape: tuple[int, ...], calls: int) -> int:
    """One JSON line per implementation: ms a call, forward and forward
    + backward, and the MiB of temporaries of each compiled program."""
    import time

    import jax
    import jax.numpy as jnp

    from tpuframe.ops.blockwise_attention import (
        blockwise_attention,
        blockwise_attention_reference,
    )
    from tpuframe.ops.ring_attention import attention_reference

    b, l, h, d = shape[:4]
    dv = shape[4] if len(shape) > 4 else d
    interpret = None if jax.default_backend() == "tpu" else True
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    qkv = tuple(jax.random.normal(k, (b, l, h, w), jnp.bfloat16) * 0.5
                for k, w in zip(keys, (d, d, dv)))
    impls = {
        "flash_kernels": lambda q, k, v: blockwise_attention(
            q, k, v, causal=True, interpret=interpret),
        "scan_schedule": lambda q, k, v: blockwise_attention_reference(
            q, k, v, causal=True),
        "attention_reference": lambda q, k, v: attention_reference(
            q, k, v, causal=True),
    }

    def ms_and_mib(fn):
        compiled = fn.lower(*qkv).compile()
        jax.block_until_ready(compiled(*qkv))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = compiled(*qkv)
        jax.block_until_ready(out)
        return ((time.perf_counter() - t0) / calls * 1e3,
                compiled.memory_analysis().temp_size_in_bytes / 2**20)

    dev = jax.devices()[0]
    for name, f in impls.items():
        rec = {"metric": "attention_standalone", "impl": name,
               "shape": [b, l, h, d, dv], "dtype": "bfloat16", "causal": True,
               "calls": calls, "backend": dev.platform,
               "device_kind": dev.device_kind,
               "pallas_interpret": bool(interpret)}
        try:
            rec["fwd_ms"], rec["fwd_temp_mib"] = ms_and_mib(jax.jit(f))
            rec["fwd_bwd_ms"], rec["fwd_bwd_temp_mib"] = ms_and_mib(jax.jit(jax.grad(
                lambda *a, f=f: jnp.sum(f(*a).astype(jnp.float32) ** 2), (0, 1, 2))))
        except Exception as e:  # a shape an implementation cannot hold
            rec["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(rec), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--standalone", default=None, metavar="B,L,H,D[,Dv]",
                    help="time the op alone at this shape (bf16, causal) "
                         "instead of pricing the train step")
    ap.add_argument("--calls", type=int, default=24,
                    help="timed calls per program of --standalone")
    ap.add_argument("--seqs", default="256,512",
                    help="comma list; each must divide the mesh seq axis")
    ap.add_argument("--warmup", type=int, default=3,
                    help="AOT warmup steps per arm (untimed)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable only: suppress stderr narration")
    args = ap.parse_args()
    if args.standalone:
        return standalone(tuple(int(x) for x in args.standalone.split(",")),
                          args.calls)

    def say(msg: str) -> None:
        if not args.json:
            print(msg, file=sys.stderr)

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu") or (
        "JAX_PLATFORMS" not in os.environ
        and not os.environ.get("TPU_NAME")
    ):
        from tpuframe.core.runtime import simulate_cpu_devices

        simulate_cpu_devices(8)

    import jax

    from tpuframe.autotune.probe import _env_overlay, probe_steps, warmup_steps
    from tpuframe.core.runtime import MeshSpec, initialize
    from tpuframe.ops import dispatch
    from tpuframe.ops.ledger import (
        attention_choice,
        open_ledger,
        price_attention,
        save_ledger,
        shape_class,
    )
    from tpuframe.track import telemetry as T

    import bench_kernels

    backend = jax.default_backend()
    interp = backend != "tpu"
    if interp:
        # only way blockwise's kernel code runs off-TPU; every arm pays
        # the same interpret tax, so the variant ordering stays fair
        os.environ["TPUFRAME_PALLAS_INTERPRET"] = "1"

    # seq-sharded mesh for the ring/ulysses arms (full/blockwise ignore
    # it — their attention is unsharded, which is exactly the regime the
    # persisted choice verdict is for)
    runtime = initialize(MeshSpec(data=1, seq=-1))
    world = runtime.device_count
    seqs = [int(x) for x in args.seqs.split(",")]
    bad = [l for l in seqs if l % world]
    if bad:
        print(json.dumps({"error": f"seqs {bad} do not divide the "
                                   f"{world}-way seq mesh axis"}))
        return 1

    persisted = bool(os.environ.get("TPUFRAME_KERNEL_LEDGER_DIR", "").strip())
    tmp_store = None
    if persisted:
        store_dir = None
        store_path = os.environ["TPUFRAME_KERNEL_LEDGER_DIR"]
    else:
        tmp_store = tempfile.mkdtemp(prefix="tpuframe_bench_attention_")
        store_dir = store_path = tmp_store

    n_steps = probe_steps() + warmup_steps()
    tele_dir = tempfile.mkdtemp(prefix="tpuframe_bench_attention_tele_")
    try:
        T.configure(jsonl_dir=tele_dir, rank=0)
        ledger = open_ledger(backend=backend, store_dir=store_dir)
        rounds = []
        for seq in seqs:
            arms: dict[str, dict] = {}
            for impl in VARIANTS:
                say(f"seq {seq}: {impl} arm…")
                mk_state, toks = make_fit(seq, impl, max_len=max(seqs))
                try:
                    arms[impl] = bench_kernels.run_fit_arm(
                        {}, mk_state, toks,
                        warmup=args.warmup, n_steps=n_steps,
                        label=f"attn_{impl}_l{seq}",
                    )
                except Exception as e:  # an impl this mesh can't run
                    arms[impl] = {"error": f"{type(e).__name__}: {e}"[:300]}
                    say(f"seq {seq}: {impl} arm failed: {arms[impl]['error']}")

            # the measured walls ARE the pricing input: each run_fn
            # replays its arm's timed window, so the persisted verdict
            # and the committed blocks come from the same steps
            def replay(impl):
                def walls_of(env, _impl=impl):
                    a = arms[_impl]
                    if "walls" not in a:  # price_attention records the error
                        raise RuntimeError(a.get("error", "arm failed"))
                    return a["walls"]
                return walls_of

            cls = shape_class(l=seq)
            verdict = price_attention(
                ledger, cls, {impl: replay(impl) for impl in VARIANTS})
            rounds.append({
                "seq": seq,
                "shape_class": cls,
                "verdict": verdict,
                "variants": {
                    impl: ({"error": a["error"]} if "error" in a else {
                        "step_time": a["step_time"],
                        "device_time": a["device_time"],
                        "recompile_events": a["recompile_events"],
                        "aot_fallback_events": a["aot_fallback_events"],
                        "aot_dispatch": a["aot_dispatch"],
                    })
                    for impl, a in arms.items()
                },
            })
            say(f"seq {seq}: choice={verdict['choice']} "
                f"p50s={ {k: round(v, 5) for k, v in verdict['p50_s'].items()} }")

        path = save_ledger(ledger, store_dir)
        say(f"ledger persisted: {path}")

        # close the loop the way models.transformer does: attn_impl="auto"
        # reads the verdict just persisted
        with _env_overlay({"TPUFRAME_KERNEL_LEDGER_DIR": store_path,
                           "TPUFRAME_KERNELS": "auto"}):
            dispatch._reset_kernel_cache()
            for r in rounds:
                r["auto_choice"] = attention_choice(r["seq"], backend=backend)
            dispatch._reset_kernel_cache()
        T.reset()
    finally:
        shutil.rmtree(tele_dir, ignore_errors=True)
        if tmp_store:
            shutil.rmtree(tmp_store, ignore_errors=True)
        if interp:
            os.environ.pop("TPUFRAME_PALLAS_INTERPRET", None)

    last = rounds[-1]
    choice = last["verdict"]["choice"]
    anchor = (last["variants"].get(choice) or {}) if choice else {}
    full_p50 = last["verdict"]["p50_s"].get("full")
    choice_p50 = last["verdict"]["p50_s"].get(choice) if choice else None
    ratio = (round(choice_p50 / full_p50, 4)
             if full_p50 and choice_p50 else None)
    clean = all(
        v.get("recompile_events") == 0 and v.get("aot_fallback_events") == 0
        for r in rounds for v in r["variants"].values() if "error" not in v
    )
    loop_closed = all(
        r["auto_choice"] == r["verdict"]["choice"] for r in rounds
    )

    rec = {
        "metric": "attention_round",
        "value": ratio,
        "unit": f"measured-choice ({choice}) step p50 / full-attention step "
                f"p50 at seq {last['seq']}",
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "mesh": {"seq": world},
        "pallas_interpret": interp,
        "ledger": {"host": ledger.host, "backend": ledger.backend,
                   "signature": ledger.signature},
        "fit": {"layers": LAYERS, "heads": HEADS, "head_dim": HEAD_DIM,
                "batch": BATCH, "steps": n_steps, "warmup": args.warmup},
        "seqs": rounds,
        "auto_dispatch_loop_closed": loop_closed,
        "clean_dispatch": clean,
        # analyzer-gateable anchor: the measured choice at the largest
        # priced seq (ratio_step_p50 / ratio_device_step, exit 3)
        "step_time": anchor.get("step_time"),
        "device_time": anchor.get("device_time"),
        "persisted": persisted,
        "store": store_path if persisted else "(tmp, discarded)",
    }
    print(json.dumps(rec, indent=1))
    if not (clean and loop_closed and choice):
        say(f"GATE: clean_dispatch={clean} loop_closed={loop_closed} "
            f"choice={choice}")
        return 3
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
