"""The kernel-profitability ledger: measured dispatch verdicts per shape.

PR 14 built the device-time top-op table and the autotune diagnosis
attaches it as a fusion target list — this module is the consumer that
closes the loop.  Three jobs:

- **Name map**: profiler op names (HLO base names off a parsed capture's
  ``top_ops`` rows) normalize to dispatchable tpuframe ops, so a
  diagnosis detail names ``cross_entropy``, not ``log_softmax_fusion``.
- **Pricing**: each kernel is A/B-probed on/off (and its tile knobs over
  a small legal grid) per ``(backend, shape-class)`` through
  ``autotune.probe``'s warmup-discarded, never-commit-slower machinery.
- **Persistence**: verdicts live next to the tuned-config store (same
  scratch root, same atomic-write/tolerant-read discipline), keyed
  ``(host, backend, plan.signature())`` — a restart on the same host
  dispatches pre-priced instead of re-probing.

``ops/dispatch.kernels_mode()`` consumes the verdicts: with
``TPUFRAME_KERNELS=auto`` (the default) every op consults
:func:`kernel_enabled`'s ledger lookup; ``on``/``off`` bypass it.  The
registry of dispatchable ops (:data:`OPS_REGISTRY`) is the lint OP
family's source of truth: every ``ops/`` kernel module must appear here
with a parity test, so an op cannot ship undispatched or untested.

Stdlib-only at module level (the knob lists ship through
``launch.remote.all_env_vars()`` and the doctor reads the ledger on
wedged-backend processes); the pricing helpers import jax lazily.
"""

# tpuframe-lint: stdlib-only

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Callable

from tpuframe.autotune.config import config_key, default_host
from tpuframe.autotune.probe import measure, run_probe

__all__ = [
    "KERNEL_ENV_VARS",
    "KERNEL_ENV_DOMAINS",
    "OPS_REGISTRY",
    "KernelLedger",
    "attn_block",
    "attention_choice",
    "ce_rows",
    "kernels_mode",
    "ledger_dir",
    "list_ledgers",
    "load_ledger",
    "map_op_name",
    "norm_tile_rows",
    "normalize_top_ops",
    "price_op",
    "shape_class",
]

#: every env knob the kernel-dispatch plane reads — aggregated by
#: ``launch.remote.all_env_vars()`` so fleet ranks dispatch identically,
#: and by ``autotune.config.all_env_domains()`` so the ledger's tile
#: probes have a lint-enforced legal grid.
KERNEL_ENV_VARS = (
    "TPUFRAME_KERNELS",
    "TPUFRAME_KERNEL_LEDGER_DIR",
    "TPUFRAME_KERNEL_CE_ROWS",
    "TPUFRAME_KERNEL_NORM_TILE_ROWS",
    "TPUFRAME_KERNEL_ATTN_BLOCK",
)

#: KN007 value domains.  The tile knobs are re-read at every op call
#: (trace time) -> "live"; the ledger store location is consulted when
#: the per-process ledger cache first loads -> "restart".
KERNEL_ENV_DOMAINS = {
    "TPUFRAME_KERNELS": {
        "type": "enum", "choices": ("auto", "on", "off"), "apply": "live"},
    "TPUFRAME_KERNEL_LEDGER_DIR": {"type": "path", "apply": "restart"},
    "TPUFRAME_KERNEL_CE_ROWS": {
        "type": "int", "range": (8, 256), "apply": "live"},
    "TPUFRAME_KERNEL_NORM_TILE_ROWS": {
        "type": "int", "range": (8, 4096), "apply": "live"},
    "TPUFRAME_KERNEL_ATTN_BLOCK": {
        "type": "int", "range": (128, 4096), "apply": "live"},
}

#: the dispatch registry: every kernel module under ``ops/`` appears
#: here with its entry point, its jnp oracle, and the parity test that
#: pins kernel == oracle.  The lint OP family cross-checks all three
#: directions (module listed, symbol exists, test exists), so this dict
#: must stay a pure literal.
OPS_REGISTRY = {
    "normalize": {
        "module": "tpuframe.ops.normalize",
        "symbol": "normalize_images",
        "reference": "normalize_images_reference",
        "parity_test": "tests/test_ops.py::test_normalize_matches_reference_uint8",
        "tile_knobs": ("TPUFRAME_KERNEL_NORM_TILE_ROWS",),
    },
    "cross_entropy": {
        "module": "tpuframe.ops.cross_entropy",
        "symbol": "fused_cross_entropy",
        "reference": "cross_entropy_reference",
        "parity_test": "tests/test_ops.py::test_fused_cross_entropy_forward",
        "tile_knobs": ("TPUFRAME_KERNEL_CE_ROWS",),
    },
    "layer_norm": {
        "module": "tpuframe.ops.layer_norm",
        "symbol": "fused_layer_norm",
        "reference": "layer_norm_reference",
        "parity_test":
            "tests/test_layer_norm.py::TestFusedLayerNorm::test_forward_matches_oracle",
        "tile_knobs": (),
    },
    "fused_adamw": {
        "module": "tpuframe.ops.fused_adamw",
        "symbol": "fused_adamw_update",
        "reference": None,
        "parity_test": "tests/test_ops.py::test_fused_adamw_update_matches_math",
        "tile_knobs": (),
    },
    "quant_wire": {
        "module": "tpuframe.ops.quant_wire",
        "symbol": "quant_encode",
        "reference": "quant_encode_reference",
        "parity_test":
            "tests/test_comms_fused.py::TestQuantWireKernels::test_amax_and_encode_bit_exact",
        "tile_knobs": (),
    },
    "blockwise_attention": {
        "module": "tpuframe.ops.blockwise_attention",
        "symbol": "blockwise_attention",
        "reference": "blockwise_attention_reference",
        "parity_test":
            "tests/test_blockwise_attention.py::test_kernel_matches_scan_schedule",
        # the kernels' tiles follow L; TPUFRAME_KERNEL_ATTN_BLOCK is the
        # block of the reference (the scan schedule) and prices nothing here
        "tile_knobs": (),
    },
    "ring_attention": {
        "module": "tpuframe.ops.ring_attention",
        "symbol": "ring_attention",
        "reference": "attention_reference",
        "parity_test": "tests/test_ring_attention.py::test_ring_matches_full",
        "tile_knobs": (),
    },
    "ulysses": {
        "module": "tpuframe.ops.ulysses",
        "symbol": "ulysses_attention",
        "reference": None,
        "parity_test": "tests/test_ulysses.py::test_ulysses_matches_full",
        "tile_knobs": (),
    },
    "grouped_matmul": {
        "module": "tpuframe.ops.grouped_matmul",
        "symbol": "grouped_matmul",
        "reference": "grouped_matmul_reference",
        "parity_test":
            "tests/test_latent_moe.py::TestGroupedMatmul::test_forward_and_both_gradients_with_empty_groups",
        "tile_knobs": (),
    },
    "moe_gating": {
        "module": "tpuframe.ops.moe_gating",
        "symbol": "moe_dispatch_combine",
        "reference": "moe_dispatch_combine_reference",
        "parity_test":
            "tests/test_moe.py::TestMoEGatingKernel::test_fused_matches_reference",
        "tile_knobs": (),
    },
}

#: the ledger's op for the whole attention family: one shape-classed
#: verdict decides which impl ``attn_impl="auto"`` dispatches.
ATTENTION_OP = "attention"


# -- knob readers -------------------------------------------------------------

def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "").strip() or default)
    except ValueError:
        return default


def _tile(name: str, default: int, lo: int, hi: int, step: int) -> int:
    """A domain-clamped, alignment-rounded tile knob read: the value is
    clipped into ``[lo, hi]`` and rounded DOWN to a multiple of ``step``
    (the TPU sublane/lane alignment the kernel's grid needs) — an
    illegal setting degrades to the nearest legal tile, never a crash."""
    v = min(hi, max(lo, _env_int(name, default)))
    return max(step, v - v % step)


def kernels_mode() -> str:
    """``TPUFRAME_KERNELS``: ``auto`` (default — consult the ledger) |
    ``on`` (every kernel the backend can run) | ``off`` (jnp references
    everywhere, the measured-escape-hatch twin of
    ``TPUFRAME_DISABLE_PALLAS``)."""
    v = os.environ.get("TPUFRAME_KERNELS", "").strip().lower()
    return v if v in ("auto", "on", "off") else "auto"


def ce_rows() -> int:
    """Rows per grid step for the cross-entropy kernels
    (``TPUFRAME_KERNEL_CE_ROWS``, default 16, sublane-aligned)."""
    return _tile("TPUFRAME_KERNEL_CE_ROWS", 16, lo=8, hi=256, step=8)


def norm_tile_rows() -> int:
    """Row-tile height for the image-normalize kernel
    (``TPUFRAME_KERNEL_NORM_TILE_ROWS``, default 256 = 128 KiB f32)."""
    return _tile("TPUFRAME_KERNEL_NORM_TILE_ROWS", 256, lo=8, hi=4096, step=8)


def attn_block() -> int:
    """Default block size of blockwise attention's scan schedule
    (``TPUFRAME_KERNEL_ATTN_BLOCK``, default 512, lane-aligned); its
    kernels' tiles follow the sequence length."""
    return _tile("TPUFRAME_KERNEL_ATTN_BLOCK", 512, lo=128, hi=4096, step=128)


# -- profiler-name -> tpuframe-op map -----------------------------------------

#: ordered (op, name tokens) pairs: the first op whose token appears in
#: a profiler base name claims the row.  Tokens are matched on the
#: lowercased base name (``device_time._base_name`` output), which for
#: XLA fusions carries the root-op hint (``log_softmax_fusion``,
#: ``layer_norm.clone``); a generic name (``fusion``, ``dot``) maps to
#: no op and keeps its raw name.
OP_NAME_TOKENS = (
    ("cross_entropy", ("cross_entropy", "log_softmax", "softmax", "nll")),
    ("layer_norm", ("layer_norm", "layernorm", "rms_norm")),
    ("fused_adamw", ("adamw", "adam")),
    ("normalize", ("normalize", "per_image_standard")),
    ("quant_wire", ("quant", "dequant", "stochastic_round")),
    (ATTENTION_OP, ("attention", "flash", "fmha", "scaled_dot_product")),
    ("grouped_matmul", ("ragged-dot", "ragged_dot", "grouped_matmul")),
    ("moe_gating", ("top_k_gating", "moe", "expert_dispatch")),
)


def map_op_name(name: str) -> str | None:
    """The tpuframe op a profiler op name belongs to, or None."""
    low = (name or "").lower()
    for op, tokens in OP_NAME_TOKENS:
        if any(tok in low for tok in tokens):
            return op
    return None


def normalize_top_ops(top_ops: list[dict]) -> list[dict]:
    """``device_time.top_ops`` rows with the profiler name normalized:
    each row gains ``op`` (the dispatchable tpuframe op, or None) and
    ``raw`` (the profiler name), and ``name`` becomes the actionable
    one — what a diagnosis detail or a dashboard should print."""
    out = []
    for row in top_ops or []:
        raw = row.get("name") or ""
        op = map_op_name(raw)
        r = dict(row)
        r["raw"] = raw
        r["op"] = op
        r["name"] = op or raw
        out.append(r)
    return out


# -- shape classes ------------------------------------------------------------

def shape_class(**dims: int) -> str | None:
    """A stable bucket for a shape: each named dim rounds UP to the next
    power of two (``shape_class(b=200, k=1000) == 'b256_k1024'``), so
    nearby shapes share one verdict and the store stays small.

    Returns None when a dim is not a concrete integer — under
    ``jax.export`` shape polymorphism the batch dims are symbolic and
    refuse ``int()`` — and dispatch degrades to its shape-agnostic
    fallback instead of aborting the export trace."""
    parts = []
    for k in sorted(dims):
        try:
            v = max(1, int(dims[k]))
        except Exception:
            return None
        p = 1
        while p < v:
            p <<= 1
        parts.append(f"{k}{p}")
    return "_".join(parts)


# -- the persisted ledger -----------------------------------------------------

def ledger_dir() -> str:
    """Where verdicts persist: ``TPUFRAME_KERNEL_LEDGER_DIR``, else a
    ``ledger/`` sibling inside the tuned-config store (same scratch
    root, same host-shared lifecycle)."""
    v = os.environ.get("TPUFRAME_KERNEL_LEDGER_DIR", "").strip()
    if v:
        return v
    from tpuframe.autotune.config import autotune_dir

    return os.path.join(autotune_dir(), "ledger")


@dataclasses.dataclass
class KernelLedger:
    """Every priced verdict for one ``(host, backend, plan signature)``.

    ``verdicts`` maps op -> shape_class -> verdict dict.  A dispatch
    verdict carries ``enable`` (the never-commit-slower outcome),
    ``env`` (winning tile-knob overrides), the measured p50s and the
    probe trail; an attention verdict carries ``choice`` (the measured
    impl) plus per-variant p50s.
    """

    host: str
    backend: str
    signature: str
    verdicts: dict[str, dict] = dataclasses.field(default_factory=dict)
    created_unix: float = 0.0

    def verdict(self, op: str, shape_cls: str) -> dict | None:
        return (self.verdicts.get(op) or {}).get(shape_cls)

    def record(self, op: str, shape_cls: str, verdict: dict) -> None:
        self.verdicts.setdefault(op, {})[shape_cls] = dict(verdict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "KernelLedger":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


def _ledger_path(host: str, backend: str, signature: str,
                 store_dir: str | None = None) -> str:
    d = store_dir or ledger_dir()
    return os.path.join(d, config_key(host, backend, signature) + ".json")


def save_ledger(ledger: KernelLedger,
                store_dir: str | None = None) -> str:
    """Atomic persist; an unwritable store degrades to un-priced
    restarts, never takes the run down (autotune-store discipline)."""
    path = _ledger_path(ledger.host, ledger.backend, ledger.signature,
                        store_dir)
    if not ledger.created_unix:
        ledger.created_unix = time.time()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(ledger.to_dict(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        return path
    return path


def load_ledger(host: str, backend: str, signature: str,
                store_dir: str | None = None) -> KernelLedger | None:
    """The persisted ledger for this identity, or None (missing store,
    corrupt JSON, identity mismatch — all read as "price fresh")."""
    path = _ledger_path(host, backend, signature, store_dir)
    try:
        with open(path) as f:
            d = json.load(f)
        led = KernelLedger.from_dict(d)
    except (OSError, ValueError, TypeError):
        return None
    if (led.host, led.backend, led.signature) != (host, backend, signature):
        return None
    return led


def list_ledgers(store_dir: str | None = None) -> list[KernelLedger]:
    """Every readable persisted ledger (doctor/CLI view)."""
    d = store_dir or ledger_dir()
    out: list[KernelLedger] = []
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(d, name)) as f:
                out.append(KernelLedger.from_dict(json.load(f)))
        except (OSError, ValueError, TypeError):
            continue
    return out


#: signature used when no ParallelPlan is in play (single-chip benches,
#: the op microbenches) — a real plan's ``signature()`` replaces it.
DEFAULT_SIGNATURE = "unplanned"


def open_ledger(*, backend: str, signature: str = DEFAULT_SIGNATURE,
                store_dir: str | None = None) -> KernelLedger:
    """Load-or-create the ledger for this host/backend/signature."""
    host = default_host()
    led = load_ledger(host, backend, signature, store_dir)
    if led is None:
        led = KernelLedger(host=host, backend=backend, signature=signature)
    return led


# -- pricing ------------------------------------------------------------------

def price_op(ledger: KernelLedger, op: str, shape_cls: str,
             run_fn: Callable[[dict], list[float]], *,
             tile_grid: dict[str, tuple] | None = None,
             guard: float | None = None) -> dict:
    """A/B-price one op for one shape class and record the verdict.

    ``run_fn(env) -> per-step walls`` runs the op's microbench under the
    probe env overlay (``autotune.probe`` owns overlay/restore and the
    warmup-discarded median).  Baseline is the reference path
    (``TPUFRAME_KERNELS=off``); the kernel commits only when its median
    beats the baseline by the guard margin, and each ``tile_grid`` value
    (knob -> candidate values, pre-clamped by the registry domain) then
    probes against the best committed config so a tile can only ever
    improve on the winning dispatch.  Never commits slower — a kernel
    that loses stays off for this shape class until re-priced.
    """
    from tpuframe.autotune.config import all_env_domains, clamp

    domains = all_env_domains()
    p50_off = measure(run_fn, {"TPUFRAME_KERNELS": "off"})
    probes = []
    on = run_probe(run_fn, {"TPUFRAME_KERNELS": "on"}, p50_off, guard=guard)
    probes.append({"env": on.env, "p50_s": on.p50_s,
                   "committed": on.committed, "reason": on.reason})
    enable = on.committed
    best_p50 = on.p50_s if enable else p50_off
    best_env: dict[str, str] = {}
    if enable:
        for knob, values in (tile_grid or {}).items():
            for value in values:
                v = clamp(knob, value, domains)
                if v is None:
                    continue
                env = {"TPUFRAME_KERNELS": "on", **best_env, knob: v}
                pr = run_probe(run_fn, env, best_p50, guard=guard)
                probes.append({"env": pr.env, "p50_s": pr.p50_s,
                               "committed": pr.committed,
                               "reason": pr.reason})
                if pr.committed:
                    best_p50 = pr.p50_s
                    best_env[knob] = v
    verdict = {
        "enable": bool(enable),
        "env": best_env,
        "p50_off_s": p50_off,
        "p50_on_s": on.p50_s,
        "p50_best_s": best_p50,
        "ratio": round(on.p50_s / p50_off, 4) if p50_off > 0 else None,
        "probes": probes,
    }
    ledger.record(op, shape_cls, verdict)
    return verdict


def price_attention(ledger: KernelLedger, shape_cls: str,
                    run_fns: dict[str, Callable[[dict], list[float]]],
                    *, unsharded: tuple = ("full", "blockwise")) -> dict:
    """Price the attention family for one shape class: measure every
    variant's median, record all of them, and pick ``choice`` — the
    fastest variant that ``attn_impl="auto"`` can legally dispatch on an
    unsharded sequence (ring/ulysses need a seq-sharded mesh, so they
    are recorded for the record but excluded from the choice)."""
    p50s: dict[str, float] = {}
    for name, fn in run_fns.items():
        try:
            p50s[name] = measure(fn, {})
        except Exception as e:  # a variant that cannot run must not win
            p50s[name] = float("inf")
            p50s[f"{name}_error"] = f"{type(e).__name__}: {e}"  # type: ignore[assignment]
    candidates = {k: v for k, v in p50s.items()
                  if k in unsharded and v != float("inf")}
    choice = min(candidates, key=candidates.get) if candidates else None
    verdict: dict[str, Any] = {
        "choice": choice,
        "p50_s": {k: v for k, v in p50s.items() if isinstance(v, float)},
        "errors": {k: v for k, v in p50s.items() if isinstance(v, str)},
    }
    ledger.record(ATTENTION_OP, shape_cls, verdict)
    return verdict


def attention_choice(seq_len: int, *, backend: str | None = None,
                     signature: str | None = None) -> str | None:
    """The measured attention impl for an unsharded sequence of
    ``seq_len``, or None when no verdict exists (callers fall back to
    the static heuristic).  Reads the process-cached ledger via the
    dispatch plane so one loud ``ops/kernel_verdict`` event fires per
    (shape class, decision)."""
    from tpuframe.ops.dispatch import _cached_ledger, _emit_verdict

    led = _cached_ledger(backend=backend, signature=signature)
    if led is None:
        return None
    cls = shape_class(l=seq_len)
    v = led.verdict(ATTENTION_OP, cls)
    choice = (v or {}).get("choice")
    if choice not in ("full", "blockwise"):
        choice = None
    _emit_verdict(ATTENTION_OP, cls, enable=choice is not None,
                  source="ledger" if v else "default", choice=choice)
    return choice
