"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, proving the distribution config is coherent
without hardware (DESIGN.md §6).

For each combo this:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. constructs the step function for the shape kind:
       train_4k    -> gradient-accumulated train_step
       prefill_32k -> prefill_fn
       decode_*    -> decode_fn (1 token + seq_len-deep state)
  3. jits with explicit in_shardings from the partition rules,
  4. ``.lower(**ShapeDtypeStruct inputs).compile()`` — any sharding
     mismatch / unsupported collective / compile-OOM fails here,
  5. records memory_analysis (fit proof), cost_analysis, and the
     collective schedule parsed from the optimized HLO.

NOTE on loop accounting: XLA's cost analysis visits while bodies ONCE;
layer scans and microbatch scans are therefore undercounted in the RAW
numbers recorded here.  The roofline harness (benchmarks/roofline.py)
lowers the per-layer body separately and applies exact trip counts —
those are the §Roofline numbers.  The raw full-step numbers are kept for
the memory-fit proof and the collective schedule.

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import os

# must precede the first jax import: the dry-run fakes a 512-chip pod
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.arch import build_arch
from repro.arch.api import SHAPES, Arch
from repro.arch.common import init_train_state, make_train_step
from repro.arch.sharding import data_axes, param_pspecs
from repro.config import get_arch_config, list_archs
from repro.launch.mesh import make_production_mesh

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(?:\(([^)]*)\)|((?:\w+)\[[\d,]*\]))\S*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# bytes-on-the-wire model per result byte (ring algorithms, large N limit)
_WIRE_FACTOR = {
    "all-gather": 1.0,        # result is the gathered tensor
    "all-reduce": 2.0,        # reduce-scatter + all-gather of operand size
    "reduce-scatter": 1.0,    # operand passes once (result is 1/N)
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def collective_schedule(hlo_text: str) -> dict:
    """Parse optimized (post-SPMD, per-device) HLO for collectives.

    Returns {kind: {"count": int, "bytes": int}} plus "total_bytes" using
    the wire model above.  ``-done`` ops are skipped (their ``-start``
    carries the shape); reduce-scatter wire bytes use operand size =
    result * N, approximated by result bytes * wire factor (documented).
    """
    out: dict = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group(4)
        type_str = m.group(2) or m.group(3) or ""
        nbytes = _shape_bytes(type_str)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0, "wire_bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes
        rec["wire_bytes"] += nbytes * _WIRE_FACTOR[kind]
    out["total_wire_bytes"] = sum(
        v["wire_bytes"] for k, v in out.items() if isinstance(v, dict)
    )
    return out


# ---------------------------------------------------------------------------
# sharding assembly
# ---------------------------------------------------------------------------


def batch_shardings(mesh: Mesh, batch_specs) -> dict:
    """Batch-dim-on-data shardings, divisibility aware."""
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def leaf(spec):
        if spec.ndim == 0:
            return NamedSharding(mesh, P())
        if spec.shape[0] % dp_size == 0 and spec.shape[0] >= dp_size:
            return NamedSharding(mesh, P(dp, *([None] * (spec.ndim - 1))))
        return NamedSharding(mesh, P(*([None] * spec.ndim)))

    return jax.tree.map(leaf, batch_specs)


def decode_state_shardings(mesh: Mesh, state_specs):
    """Generic decode-state policy: dim0 = layer stack (replicated),
    dim1 = batch on data axes if divisible, largest remaining divisible
    dim on "model" (KV caches shard their seq dim; SSM states their
    state dim) — DESIGN.md §6."""
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    m_size = mesh.shape["model"]

    def leaf(spec):
        nd = spec.ndim
        entries: list = [None] * nd
        if nd >= 2 and spec.shape[1] % dp_size == 0 and spec.shape[1] >= dp_size:
            entries[1] = dp
        if nd >= 3:
            dims = sorted(range(2, nd), key=lambda i: -spec.shape[i])
            for dim in dims:
                if spec.shape[dim] % m_size == 0 and spec.shape[dim] >= m_size:
                    entries[dim] = "model"
                    break
        return NamedSharding(mesh, P(*entries))

    return jax.tree.map(leaf, state_specs)


# ---------------------------------------------------------------------------
# dry-run per combo
# ---------------------------------------------------------------------------


def build_step(arch: Arch, shape_name: str, mesh: Mesh, *, num_microbatches: int = 16):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    cfg = arch.cfg
    sh = SHAPES[shape_name]
    params_spec = jax.eval_shape(arch.init_params, jax.random.PRNGKey(0))
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if sh.kind == "train":
        # ZeRO/FSDP: when params+adam (16 bytes/param) exceed the HBM
        # budget under pure tensor parallelism, additionally shard the
        # train state over the data axes (weights all-gather per layer,
        # grads reduce-scatter — GSPMD derives both from the specs).
        state_bytes_tp = cfg.param_count() * 16 / mesh.shape["model"]
        if state_bytes_tp > 8e9:
            pspecs = param_pspecs(params_spec, axis_size=mesh.shape["model"],
                                  fsdp_axes=dp, fsdp_size=dp_size)
        else:
            pspecs = param_pspecs(params_spec, axis_size=mesh.shape["model"])
    else:
        # serving: bf16 weights + FSDP over the data axes (weights
        # all-gather on use; the data axis otherwise only carries batch)
        params_spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype
            ),
            params_spec,
        )
        pspecs = param_pspecs(
            params_spec, axis_size=mesh.shape["model"],
            fsdp_axes=dp, fsdp_size=dp_size,
        )
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    batch_specs = arch.input_specs(shape_name)
    batch_sh = batch_shardings(mesh, batch_specs)

    if sh.kind == "train":
        # rows per microbatch must stay divisible by the data-axis size
        mb = min(num_microbatches, max(sh.global_batch // dp_size, 1))
        while sh.global_batch % mb:
            mb //= 2
        step = make_train_step(arch.loss_fn, num_microbatches=mb, lr=1e-4,
                               data_axes=dp)
        state_spec = jax.eval_shape(init_train_state, params_spec)
        state_sh = jax.tree.map(
            lambda leaf_spec: None, state_spec)  # placeholder, built below
        state_sh = {
            "params": param_sh, "m": param_sh, "v": param_sh,
            "step": NamedSharding(mesh, P()),
        }
        from repro.arch.common import TrainState

        state_sharding = TrainState(
            params=param_sh, m=param_sh, v=param_sh, step=NamedSharding(mesh, P())
        )
        fn = jax.jit(step, in_shardings=(state_sharding, batch_sh), donate_argnums=0)
        return fn, (state_spec, batch_specs)

    if sh.kind == "prefill":
        fn = jax.jit(arch.prefill_fn, in_shardings=(param_sh, batch_sh))
        return fn, (params_spec, batch_specs)

    # decode
    state_specs = jax.eval_shape(
        lambda p: arch.init_decode_state(p, sh.global_batch, sh.seq_len), params_spec
    )
    state_sh = decode_state_shardings(mesh, state_specs)
    fn = jax.jit(arch.decode_fn, in_shardings=(param_sh, state_sh, batch_sh),
                 donate_argnums=1)
    return fn, (params_spec, state_specs, batch_specs)


def dryrun_one(arch_name: str, shape_name: str, *, multi_pod: bool = False,
               num_microbatches: int = 16, save: bool = True, verbose: bool = True) -> dict:
    cfg = get_arch_config(arch_name)
    arch = build_arch(cfg)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: dict = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "family": cfg.family, "status": "skipped",
    }
    if not arch.supports(shape_name):
        rec["reason"] = "full-attention arch; long_500k requires sub-quadratic attention (DESIGN.md §4)"
        if save:
            _save(rec)
        return rec

    from repro.arch.sharding import activation_policy

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh, activation_policy(data_axes(mesh)):
        fn, args = build_step(arch, shape_name, mesh, num_microbatches=num_microbatches)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = collective_schedule(hlo)
    n_dev = len(mesh.devices.reshape(-1))
    rec.update(
        status="ok",
        devices=n_dev,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "total_per_device_bytes": int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
            ),
        },
        raw_cost={  # per-device, while-bodies counted once (see module doc)
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        },
        collectives=colls,
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
    )
    if verbose:
        fit = rec["memory"]["total_per_device_bytes"] / 16e9  # v5e 16 GB HBM
        print(
            f"[{arch_name} | {shape_name} | {mesh_name}] OK "
            f"compile={t_compile:.1f}s mem/dev={rec['memory']['total_per_device_bytes']/1e9:.2f}GB "
            f"({fit*100:.0f}% of v5e HBM) collectives={ {k: v['count'] for k, v in colls.items() if isinstance(v, dict)} }"
        )
    if save:
        _save(rec)
    return rec


def _save(rec: dict):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    (OUT_DIR / name).write_text(json.dumps(rec, indent=2))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None], help="input shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=16)
    args = ap.parse_args()

    archs = [args.arch] if args.arch else [a for a in list_archs() if a != "glucose-lstm"]
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    dryrun_one(arch, shape, multi_pod=mp, num_microbatches=args.microbatches)
                except Exception as e:  # noqa: BLE001 — report and continue
                    print(f"[{arch} | {shape} | multi_pod={mp}] FAILED: {type(e).__name__}: {e}")
                    failures.append((arch, shape, mp, str(e)[:200]))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nALL DRY-RUNS OK")


if __name__ == "__main__":
    main()
