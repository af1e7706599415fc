"""The comparison that decides ``correct``: the program's first three
steps against the configuration's plain reference.

The harness makes the weights from the seed (``init_params``), gives the
same weights to the program and to the reference, and feeds both the
same first three batches.  Compared, each against a limit of its own from
the configuration's file:

* ``loss_gap``    - largest |program loss - reference loss| over the steps;
* ``grad_gap``    - worst leaf of | ||g1||_program - ||g1||_reference | over
  max(that leaf's reference norm, the median leaf's), where g1 is the first
  gradient as the optimizer got it (from its state after one step);
* ``update_gap``  - the same for the parameters' change after three steps;
* ``grad_diff``   - for each of the configuration's ``probe_leaves``,
  ||g1_program - g1_reference|| / ||g1_reference||, the leaf kept whole and
  held to a limit of its own.

The two norm gaps are taken over the leaves of more than ``SMALL_LEAF``
elements: a norm averages rounding error away (it enters at second order),
and the small leaves (BatchNorm and LayerNorm scales and biases) are sums
that cancel almost wholly, chaotic at 20-40% in bfloat16 and in float8
alike (chip readings, PERF.md section 2).  They catch a fault of structure:
rows left out, an exchange skipped, a step that changes nothing.  Precision
shows at first order in ``grad_diff``.

``control_wrap`` computes every matmul and convolution of the reference,
forward and backward, on operands rounded to the precision below the
configuration's (fp8 e4m3 for bfloat16), which the limits must refuse.
"""

from __future__ import annotations

import importlib.util
import os
import statistics

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench.reference import optim

_HERE = os.path.dirname(os.path.abspath(__file__))
N_STEPS = 3
#: leaves up to this many elements are left out of the norm gaps
SMALL_LEAF = 8192


def load_by_name(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold ``-``, ``.``)."""
    path = os.path.join(_HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def init_params(shapes: dict, seed: int, sharding=None):
    """Every leaf from the seed in one jitted call, float32."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_spec)

    def make(key):
        out = []
        for i, (shape, init) in enumerate(leaves):
            if init == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif init == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            elif init[0] == "const":
                out.append(jnp.full(shape, init[1], jnp.float32))
            else:
                out.append(init[1] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32))
        return jax.tree.unflatten(treedef, out)

    key = jax.random.PRNGKey(fold_seed(seed))
    return jax.jit(make, out_shardings=sharding)(key)


def fold_seed(seed: int) -> int:
    """Any whole number -> a 31-bit seed jax and the Trainer both take."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) & 0x7FFFFFFF


# -- lower precisions, put in the reference's place -----------------------
def _round_fp8(x):
    """float8 e4m3 with a per-tensor scale (amax -> 448), as fp8 training does."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(x.dtype)


def rounded_operands(rnd):
    """Decorator for a bilinear op ``f(a, b)``: computes it, forward and
    backward, on operands rounded by ``rnd`` - the forward product from
    rounded inputs, both backward products from the rounded cotangent."""
    def wrap(f):
        @jax.custom_vjp
        def op(a, b):
            return f(rnd(a), rnd(b))

        def fwd(a, b):
            return jax.vjp(f, rnd(a), rnd(b))

        def bwd(vjp, dy):
            return vjp(rnd(dy))

        op.defvjp(fwd, bwd)
        return op
    return wrap


#: the control: the nearest precision below the configurations' bfloat16
control_wrap = rounded_operands(_round_fp8)
#: the configurations' own precision, for reading how far bf16 alone moves the numbers
bf16_wrap = rounded_operands(_round_bf16)


def leaf_norms(tree) -> dict:
    """{'/'-joined path: l2 norm} of every leaf, computed on the device."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in flat
    }


def diff_norms(new, old) -> dict:
    return leaf_norms(jax.tree.map(lambda a, b: a - b, new, old))


def leaf_paths(tree) -> dict:
    """{'/'-joined path: leaf}."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path): leaf
            for path, leaf in flat}


def pick_leaves(tree, names) -> dict:
    """Copies of the named leaves (the tree itself may be donated next step)."""
    leaves = leaf_paths(tree)
    return {n: leaves[n] + 0 for n in names}


def rel_diff(got: dict, want: dict) -> dict:
    """{name: ||got - want|| / ||want||} of whole leaves.  Taken on the host
    in float64: the two sides may live on meshes that order the chips
    differently, and the probe leaves are a few MB."""
    out = {}
    for n, w in want.items():
        g = np.asarray(jax.device_get(got[n]), np.float64)
        w = np.asarray(jax.device_get(w), np.float64)
        out[n] = float(np.linalg.norm(g - w) / np.linalg.norm(w))
    return out


def _blocked(x: np.ndarray, rows: int, n_dev: int) -> np.ndarray:
    """(G, ...) -> (blocks, n_dev * rows, ...), each device's rows kept on it."""
    per_dev = x.shape[0] // n_dev
    blocks = per_dev // rows
    x = x.reshape((n_dev, blocks, rows) + x.shape[1:])
    return np.swapaxes(x, 0, 1).reshape((blocks, n_dev * rows) + x.shape[3:])


def follow_reference(ref, cfg: dict, seed: int, batches, wrap=None) -> dict:
    """Drive the reference through the first ``len(batches)`` steps.

    Returns losses per step, the first gradient's leaf norms, the leaf
    norms of the parameters' change, the leaves' sizes and, whole and still
    on the device under ``_kept``, the first gradient's ``probe_leaves``.  Runs in blocks of
    ``reference.rows_per_block`` rows per device (0: the whole batch, for
    models whose BatchNorm couples the rows) over every local device.
    """
    wrap = wrap or (lambda f: f)
    opt = cfg["optimizer"]
    rcfg = cfg["reference"]
    devices = jax.local_devices()
    mesh = Mesh(np.array(devices), ("data",))
    replicated = NamedSharding(mesh, P())
    rows = int(rcfg["rows_per_block"])

    def loss_fn(params, x, y):
        return ref.loss(params, x, y, cfg, wrap, rcfg["remat"])

    def grads_of(params, x, y):
        if not rows:
            return jax.value_and_grad(loss_fn)(params, x, y)

        def body(acc, xy):
            l, g = jax.value_and_grad(loss_fn)(params, *xy)
            return (acc[0] + l, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(body, zero, (x, y))
        n = x.shape[0]
        return l / n, jax.tree.map(lambda a: a / n, g)

    @jax.jit
    def step(params, state, x, y):
        l, g = grads_of(params, x, y)
        new, state = optim.update(opt, params, g, state)
        return new, state, l, g

    def place(a):
        a = np.asarray(a)
        if rows:
            a = _blocked(a, rows, len(devices))
            spec = P(None, "data")
        else:
            spec = P("data")
        return jax.device_put(a, NamedSharding(mesh, spec))

    params0 = init_params(ref.param_shapes(cfg), seed, replicated)
    params, state = params0, optim.init(opt, params0)
    losses, g1, kept = [], None, None
    for x, y in batches:
        params, state, l, g = step(params, state, place(x), place(y))
        losses.append(l)
        if g1 is None:
            g1 = jax.jit(leaf_norms)(g)
            kept = pick_leaves(g, cfg["probe_leaves"])
        del g
    delta = jax.jit(diff_norms)(params, params0)
    sizes = {k: int(v.size) for k, v in leaf_paths(params0).items()}
    out = jax.device_get({"loss": losses, "grad": g1, "update": delta})
    return {"loss": [float(v) for v in out["loss"]],
            "grad": {k: float(v) for k, v in out["grad"].items()},
            "update": {k: float(v) for k, v in out["update"].items()},
            "sizes": sizes, "_kept": kept}


def norm_gap(got: dict, want: dict, sizes: dict | None = None) -> tuple[float, str]:
    """Worst leaf of |got - want| / max(want_leaf, median want), over the
    leaves of more than ``SMALL_LEAF`` elements where ``sizes`` is given."""
    if sizes:
        want = {k: w for k, w in want.items() if sizes[k] > SMALL_LEAF}
    floor = statistics.median(want.values())
    worst, where = 0.0, ""
    for k, w in want.items():
        gap = abs(got.get(k, 0.0) - w) / max(w, floor, 1e-30)
        if gap != gap:  # a NaN anywhere is the worst there is
            return gap, k
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(program: dict, reference: dict, limits: dict) -> tuple[bool, list[dict]]:
    """Each number beside its limit; ``ok`` only if every one is inside.

    ``limits["grad_diff"]`` is one limit for every probe leaf or a limit for
    each by name: leaves at different depths sit at different distances from
    float32, and each is held at its own."""
    loss_gap = max(abs(a - b) for a, b in zip(program["loss"], reference["loss"]))
    sizes = reference["sizes"]
    grad_gap, grad_at = norm_gap(program["grad"], reference["grad"], sizes)
    upd_gap, upd_at = norm_gap(program["update"], reference["update"], sizes)
    rows = [
        {"number": "loss_gap", "value": loss_gap, "limit": limits["loss_gap"], "at": "max over steps"},
        {"number": "grad_gap", "value": grad_gap, "limit": limits["grad_gap"], "at": grad_at},
        {"number": "update_gap", "value": upd_gap, "limit": limits["update_gap"], "at": upd_at},
    ]
    by_leaf = limits["grad_diff"]
    for leaf, value in program["grad_diff"].items():
        limit = by_leaf.get(leaf) if isinstance(by_leaf, dict) else by_leaf
        rows.append({"number": "grad_diff", "value": value, "limit": limit, "at": leaf})
    for r in rows:
        if r["limit"] is None:
            raise ValueError(f"the configuration's tolerance has no limit for {r['number']} "
                             f"({r['at']})")
        r["ok"] = bool(r["value"] <= r["limit"])  # NaN fails
    ok = all(r["ok"] for r in rows) and len(rows) > 3 \
        and len(program["loss"]) == len(reference["loss"]) == N_STEPS
    return ok, rows
