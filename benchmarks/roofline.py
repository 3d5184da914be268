import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Roofline analysis (harness §Roofline): per (arch x input shape),
derive the three roofline terms from compiled dry-run artifacts on the
single-pod production mesh.

Methodology (loop-corrected component lowering — see
launch/dryrun.py's note on XLA while-body cost semantics):

  * ONE layer of each kind is lowered + compiled under the production
    mesh and sharding rules, inside ``unroll_scans()`` so inner loops
    (flash-attention KV blocks, SSD chunk recurrence) are fully present
    in the HLO.  For train shapes the lowered function is
    grad(remat(layer)) — forward + recompute + backward, exactly what
    one layer costs inside the real train step.
  * The embedding + LM-head + loss path is lowered separately.
  * Totals: layer cost x num_layers x num_microbatches + head cost x
    num_microbatches (+ analytic optimizer-update bytes/FLOPs).
  * Collective bytes come from the same compiled artifacts
    (launch.dryrun.collective_schedule) with identical multipliers.

Terms (v5e constants):
    compute_s    = device_FLOPs / 197e12
    memory_s     = device_bytes_accessed / 819e9
    collective_s = device_collective_wire_bytes / 50e9

Output: experiments/roofline/<arch>__<shape>.json + a printed table.
"""
import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.arch import build_arch
from repro.arch.api import SHAPES
from repro.arch.sharding import activation_policy, data_axes, param_pspecs
from repro.config import get_arch_config, list_archs
from repro.launch.dryrun import batch_shardings, collective_schedule, decode_state_shardings
from repro.launch.mesh import make_production_mesh
from repro.nn.unroll import unroll_scans

PEAK_FLOPS = 197e12     # bf16 / chip
HBM_BW = 819e9          # B/s / chip
ICI_BW = 50e9           # B/s / link

OUT_DIR = Path(__file__).resolve().parents[1] / "experiments" / "roofline"

# override for §Perf microbatch-count experiments (None = B // dp_size)
TRAIN_MB_OVERRIDE: int | None = None

# attention-internal sharding pinning (§Perf H1: 22x collective win on
# 32k full-attention prefill).  False reproduces the baseline table
# (archived in experiments/roofline_baseline/).
ATTN_PIN = True


def _policy(dp, *, train: bool = False):
    if ATTN_PIN:
        return activation_policy(dp, attn_axis="model", attn_axis_size=16,
                                 attn_seq_fallback=not train)
    return activation_policy(dp)


def _cost(compiled) -> dict:
    c = compiled.cost_analysis()
    colls = collective_schedule(compiled.as_text())
    return {
        "flops": float(c.get("flops", 0.0)),
        "bytes": float(c.get("bytes accessed", 0.0)),
        "coll_bytes": float(colls.get("total_wire_bytes", 0.0)),
        "colls": {k: v["count"] for k, v in colls.items() if isinstance(v, dict)},
    }


def _scale(c: dict, mult: float) -> dict:
    return {
        "flops": c["flops"] * mult,
        "bytes": c["bytes"] * mult,
        "coll_bytes": c["coll_bytes"] * mult,
    }


def _add(*cs) -> dict:
    out = {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0}
    for c in cs:
        for k in out:
            out[k] += c[k]
    return out


def _param_shardings(mesh, spec_tree, *, fsdp: bool):
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    rules = param_pspecs(
        spec_tree, axis_size=mesh.shape["model"],
        fsdp_axes=dp if fsdp else (), fsdp_size=dp_size if fsdp else 1,
    )
    return jax.tree.map(lambda s: NamedSharding(mesh, s), rules,
                        is_leaf=lambda x: isinstance(x, P))


def _sds(tree):
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


# ---------------------------------------------------------------------------
# per-family component lowering
# ---------------------------------------------------------------------------


def _layer_cost(arch, shape_name: str, mesh) -> tuple[dict, float]:
    """(per-layer compiled cost, layer multiplier)."""
    cfg = arch.cfg
    sh = SHAPES[shape_name]
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    train = sh.kind == "train"
    mb = max(sh.global_batch // dp_size, 1) if train else sh.global_batch
    if train and TRAIN_MB_OVERRIDE:
        mb = TRAIN_MB_OVERRIDE
    rows = sh.global_batch // mb if train else sh.global_batch  # rows per lowered call
    seq = sh.seq_len
    dtype = jnp.dtype(cfg.dtype)

    # single-layer params (template from eval_shape of one layer)
    if cfg.family in ("dense", "moe", "vlm"):
        from repro.arch import lm

        lp_spec = jax.eval_shape(lambda k: lm.init_layer(k, cfg), jax.random.PRNGKey(0))
        positions = jnp.arange(seq)

        def fwd(lp, x):
            out, _, _ = lm.layer_forward(x, lp, cfg, positions)
            return out

        mult = cfg.num_layers * (mb if train else 1)
    elif cfg.family == "ssm":
        from repro.nn.ssm import init_mamba2_block, mamba2_block
        from repro.arch.ssm_lm import _dims

        dims = _dims(cfg)
        lp_spec = jax.eval_shape(
            lambda k: {"mamba": init_mamba2_block(k, cfg.d_model, **dims)},
            jax.random.PRNGKey(0),
        )

        def fwd(lp, x):
            return x + mamba2_block(x, lp["mamba"], chunk=cfg.ssm_chunk, **dims)

        mult = cfg.num_layers * (mb if train else 1)
    elif cfg.family == "hybrid":
        from repro.arch import hybrid_lm

        full_spec = jax.eval_shape(
            lambda k: hybrid_lm.init_params(k, cfg), jax.random.PRNGKey(0)
        )
        lp_spec = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                               full_spec["blocks"])
        positions = jnp.arange(seq)

        def fwd(sp, x):
            out, _ = hybrid_lm._super_forward(x, sp, cfg, positions)
            return out

        mult = hybrid_lm.num_super_blocks(cfg) * (mb if train else 1)
    elif cfg.family == "encdec":
        # decoder layer dominates (encoder seq 1500 << decoder 4k/32k);
        # encoder cost added via the enc/dec layer ratio below.
        from repro.arch import encdec

        full_spec = jax.eval_shape(
            lambda k: encdec.init_params(k, cfg), jax.random.PRNGKey(0)
        )
        lp_spec = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                               full_spec["dec_layers"])
        enc_out_spec = jax.ShapeDtypeStruct((rows, cfg.encoder_seq, cfg.d_model), dtype)

        from repro.nn.layers import gelu_ffn, layer_norm

        def fwd(lp, x, enc_out):
            h = layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
            x = x + encdec._mha(h, lp["self_attn"], cfg, causal=True)
            h = layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
            x = x + encdec._mha(h, lp["cross_attn"], cfg, kv=enc_out, causal=False)
            h = layer_norm(x, lp["ln3"]["scale"], lp["ln3"]["bias"])
            return x + gelu_ffn(h, lp["mlp"])

        mult = cfg.num_layers * (mb if train else 1)
    else:
        raise KeyError(cfg.family)

    x_spec = jax.ShapeDtypeStruct((rows, seq, cfg.d_model), dtype)
    lp_sh = _param_shardings(mesh, lp_spec, fsdp=not train)
    x_sh = NamedSharding(mesh, P(dp, None, None) if rows % dp_size == 0 else P())

    if train:
        if cfg.family == "encdec":
            def step(lp, x, eo):
                f = jax.checkpoint(
                    lambda lp_, x_, eo_: jnp.sum(fwd(lp_, x_, eo_).astype(jnp.float32))
                )
                return jax.grad(f, argnums=(0, 1, 2))(lp, x, eo)

            eo_sh = x_sh if rows % dp_size == 0 else NamedSharding(mesh, P())
            args = (lp_spec, x_spec, enc_out_spec)
            shardings = (lp_sh, x_sh, eo_sh)
        else:
            def step(lp, x):
                f = jax.checkpoint(lambda lp_, x_: jnp.sum(fwd(lp_, x_).astype(jnp.float32)))
                return jax.grad(f, argnums=(0, 1))(lp, x)

            args = (lp_spec, x_spec)
            shardings = (lp_sh, x_sh)
    else:
        if cfg.family == "encdec":
            step = lambda lp, x, eo: fwd(lp, x, eo)
            args = (lp_spec, x_spec, enc_out_spec)
            shardings = (lp_sh, x_sh, x_sh if rows % dp_size == 0 else NamedSharding(mesh, P()))
        else:
            step = fwd
            args = (lp_spec, x_spec)
            shardings = (lp_sh, x_sh)

    with mesh, _policy(dp, train=train), unroll_scans():
        compiled = jax.jit(step, in_shardings=shardings).lower(*args).compile()
    return _cost(compiled), mult


def _decode_layer_cost(arch, shape_name: str, mesh) -> tuple[dict, float]:
    """One decode step's per-layer cost via the full decode_fn divided by
    L is unreliable (loop-once) — instead lower a single layer decode."""
    cfg = arch.cfg
    sh = SHAPES[shape_name]
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bsz = sh.global_batch
    dtype = jnp.dtype(cfg.dtype)
    b_shardable = bsz % dp_size == 0 and bsz >= dp_size

    # whole-model decode state; slice layer 0 for the single-layer call
    params_spec = jax.eval_shape(arch.init_params, jax.random.PRNGKey(0))
    state_spec = jax.eval_shape(
        lambda p: arch.init_decode_state(p, bsz, sh.seq_len), params_spec
    )
    state_l0 = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), state_spec
    )
    state_sh_full = decode_state_shardings(mesh, state_spec)
    state_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, P(*s.spec[1:])), state_sh_full
    )

    x_spec = jax.ShapeDtypeStruct((bsz, 1, cfg.d_model), dtype)
    x_sh = NamedSharding(mesh, P(dp, None, None) if b_shardable else P())
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    if cfg.family in ("dense", "moe", "vlm"):
        from repro.arch import lm

        lp_spec = jax.eval_shape(lambda k: lm.init_layer(k, cfg), jax.random.PRNGKey(0))

        def step(lp, cache, x, pos):
            return lm.layer_decode(x, lp, cache, cfg, pos)

        mult = cfg.num_layers
    elif cfg.family == "ssm":
        from repro.nn.ssm import init_mamba2_block, mamba2_decode
        from repro.arch.ssm_lm import _dims

        dims = _dims(cfg)
        lp_spec = jax.eval_shape(
            lambda k: init_mamba2_block(k, cfg.d_model, **dims), jax.random.PRNGKey(0)
        )

        def step(lp, st, x, pos):
            return mamba2_decode(x[:, 0, :], lp, st, **dims)

        mult = cfg.num_layers
    elif cfg.family == "hybrid":
        from repro.arch import hybrid_lm

        full_spec = jax.eval_shape(
            lambda k: hybrid_lm.init_params(k, cfg), jax.random.PRNGKey(0)
        )
        lp_spec = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                               full_spec["blocks"])

        # reuse the scan body by calling decode on a 1-super-block model
        def step(sp, st, x, pos):
            import jax.numpy as jnp_

            from repro.nn.layers import dense, rms_norm, rope, swiglu_ffn
            from repro.nn.attention import decode_attention
            from repro.nn.rglru import recurrent_block_decode

            pat = hybrid_lm._pattern(cfg)
            new_st = dict(st)
            for i, kind in enumerate(pat):
                bp = sp[i]
                h = rms_norm(x, bp["ln1_scale"], cfg.norm_eps)
                if kind == "rglru":
                    out, new_st[f"rec{i}"] = recurrent_block_decode(
                        h[:, 0, :], bp["mix"]["rec"], st[f"rec{i}"]
                    )
                    mix = out[:, None, :]
                else:
                    b = x.shape[0]
                    hh, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
                    q = dense(h, bp["mix"]["wq"]).reshape(b, 1, hh, hd)
                    k = dense(h, bp["mix"]["wk"]).reshape(b, 1, kh, hd)
                    v = dense(h, bp["mix"]["wv"]).reshape(b, 1, kh, hd)
                    q = rope(q, pos.reshape(1), cfg.rope_theta)
                    k = rope(k, pos.reshape(1), cfg.rope_theta)
                    cache = st[f"kv{i}"].append(k, v)
                    attn = decode_attention(q, cache, window=cfg.local_attn_window)
                    new_st[f"kv{i}"] = cache
                    mix = dense(attn.reshape(b, 1, -1), bp["mix"]["wo"])
                x = x + mix
                h = rms_norm(x, bp["ln2_scale"], cfg.norm_eps)
                x = x + swiglu_ffn(h, bp["mlp"])
            return x, new_st

        mult = hybrid_lm.num_super_blocks(cfg)
    elif cfg.family == "encdec":
        from repro.arch import encdec
        from repro.nn.attention import decode_attention, plain_attention
        from repro.nn.layers import dense, gelu_ffn, layer_norm

        full_spec = jax.eval_shape(
            lambda k: encdec.init_params(k, cfg), jax.random.PRNGKey(0)
        )
        lp_spec = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
                               full_spec["dec_layers"])

        def step(lp, st, x, pos):
            b = x.shape[0]
            h, hd = cfg.num_heads, cfg.head_dim
            hst = layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
            q = dense(hst, lp["self_attn"]["wq"], lp["self_attn"]["bq"]).reshape(b, 1, h, hd)
            k = dense(hst, lp["self_attn"]["wk"]).reshape(b, 1, h, hd)
            v = dense(hst, lp["self_attn"]["wv"], lp["self_attn"]["bv"]).reshape(b, 1, h, hd)
            cache = st["self"].append(k, v)
            attn = decode_attention(q, cache)
            x = x + dense(attn.reshape(b, 1, -1), lp["self_attn"]["wo"], lp["self_attn"]["bo"])
            hst = layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
            qc = dense(hst, lp["cross_attn"]["wq"], lp["cross_attn"]["bq"]).reshape(b, 1, h, hd)
            cattn = plain_attention(qc, st["cross"]["k"], st["cross"]["v"], causal=False)
            x = x + dense(cattn.reshape(b, 1, -1), lp["cross_attn"]["wo"], lp["cross_attn"]["bo"])
            hst = layer_norm(x, lp["ln3"]["scale"], lp["ln3"]["bias"])
            return x + gelu_ffn(hst, lp["mlp"]), cache

        state_l0 = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
            {"self": state_spec["self"], "cross": state_spec["cross"]},
        )
        state_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, P(*s.spec[1:])),
            {"self": state_sh_full["self"], "cross": state_sh_full["cross"]},
        )
        mult = cfg.num_layers
    else:
        raise KeyError(cfg.family)

    lp_sh = _param_shardings(mesh, lp_spec, fsdp=True)
    with mesh, _policy(dp), unroll_scans():
        compiled = (
            jax.jit(step, in_shardings=(lp_sh, state_sh, x_sh, NamedSharding(mesh, P())))
            .lower(lp_spec, state_l0, x_spec, pos)
            .compile()
        )
    return _cost(compiled), mult


def _head_cost(arch, shape_name: str, mesh) -> dict:
    """Embedding + LM head + loss (train) or head only (serve)."""
    cfg = arch.cfg
    sh = SHAPES[shape_name]
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    train = sh.kind == "train"
    mb = max(sh.global_batch // dp_size, 1) if train else 1
    if train and TRAIN_MB_OVERRIDE:
        mb = TRAIN_MB_OVERRIDE
    rows = sh.global_batch // mb if train else sh.global_batch
    seq = 1 if sh.kind == "decode" else sh.seq_len
    dtype = jnp.dtype(cfg.dtype)
    from repro.nn.layers import pad_vocab

    vp = pad_vocab(cfg.vocab_size)
    d = cfg.d_model
    emb_spec = {
        "embed": jax.ShapeDtypeStruct((vp, d), jnp.float32 if train else dtype),
        "lm_head": jax.ShapeDtypeStruct((d, vp), jnp.float32 if train else dtype),
    }
    emb_sh = _param_shardings(mesh, emb_spec, fsdp=not train)
    tok_spec = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    b_shardable = rows % dp_size == 0 and rows >= dp_size
    tok_sh = NamedSharding(mesh, P(dp, None) if b_shardable else P())

    from repro.arch.common import cross_entropy

    if train:
        def head(p, tokens, labels):
            x = jnp.take(p["embed"], tokens, axis=0).astype(dtype)
            # stand-in residual: embedding feeds the head directly; the
            # layer stack cost is accounted separately
            logits = x @ p["lm_head"].astype(x.dtype)
            return cross_entropy(logits, labels)

        fn = jax.grad(head, argnums=0)
        args = (emb_spec, tok_spec, tok_spec)
        shardings = (emb_sh, tok_sh, tok_sh)
        mult = mb
    else:
        def head(p, tokens):
            x = jnp.take(p["embed"], tokens, axis=0).astype(dtype)
            return x @ p["lm_head"].astype(x.dtype)

        fn = head
        args = (emb_spec, tok_spec)
        shardings = (emb_sh, tok_sh)
        mult = 1

    with mesh, _policy(dp):
        compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
    return _scale(_cost(compiled), mult)


def analyze(arch_name: str, shape_name: str, *, save: bool = True, verbose: bool = True) -> dict:
    cfg = get_arch_config(arch_name)
    arch = build_arch(cfg)
    sh = SHAPES[shape_name]
    rec = {"arch": arch_name, "shape": shape_name, "mesh": "pod16x16", "status": "skipped"}
    if not arch.supports(shape_name):
        rec["reason"] = "long_500k requires sub-quadratic attention"
        if save:
            _save(rec)
        return rec

    mesh = make_production_mesh(multi_pod=False)
    n_dev = 256
    if sh.kind == "decode":
        layer, mult = _decode_layer_cost(arch, shape_name, mesh)
    else:
        layer, mult = _layer_cost(arch, shape_name, mesh)
    head = _head_cost(arch, shape_name, mesh)
    total = _add(_scale(layer, mult), head)

    if sh.kind == "train":
        # optimizer update (analytic): adam reads p,m,v,g + writes p,m,v
        pcount_dev = cfg.param_count() / n_dev  # FSDP or TP — amortized view
        total["bytes"] += 28.0 * pcount_dev
        total["flops"] += 10.0 * pcount_dev
        # and whisper's encoder stack (decoder layer was lowered above)
        if cfg.family == "encdec":
            total = _add(total, _scale(layer, 0.35 * mult))  # enc ~1500/4096 of dec cost

    compute_s = total["flops"] / PEAK_FLOPS
    memory_s = total["bytes"] / HBM_BW
    coll_s = total["coll_bytes"] / ICI_BW
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", coll_s),
        key=lambda kv: kv[1],
    )[0]

    tokens = sh.global_batch * (sh.seq_len if sh.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    if sh.kind == "train":
        model_flops = 6 * n_active * tokens
    elif sh.kind == "prefill":
        model_flops = 2 * n_active * tokens
    else:
        model_flops = 2 * n_active * tokens
    device_model_flops = model_flops / n_dev
    useful = device_model_flops / total["flops"] if total["flops"] else 0.0

    rec.update(
        status="ok",
        kind=sh.kind,
        layer_mult=mult,
        per_device={
            "flops": total["flops"], "bytes": total["bytes"],
            "collective_wire_bytes": total["coll_bytes"],
        },
        terms_s={
            "compute": compute_s, "memory": memory_s, "collective": coll_s,
        },
        dominant=dominant,
        model_flops_global=model_flops,
        useful_flop_ratio=useful,
        layer_collectives=layer["colls"],
    )
    if verbose:
        print(
            f"[{arch_name:24s} {shape_name:12s}] compute {compute_s*1e3:9.3f}ms | "
            f"memory {memory_s*1e3:9.3f}ms | collective {coll_s*1e3:9.3f}ms | "
            f"dominant={dominant:10s} | useful={useful:5.2f}"
        )
    if save:
        _save(rec)
    return rec


def _save(rec):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{rec['arch']}__{rec['shape']}.json").write_text(json.dumps(rec, indent=2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    args = ap.parse_args()
    archs = [args.arch] if args.arch else [a for a in list_archs() if a != "glucose-lstm"]
    shapes = [args.shape] if args.shape else list(SHAPES)
    failures = []
    for a in archs:
        for s in shapes:
            try:
                analyze(a, s)
            except Exception as e:  # noqa: BLE001
                print(f"[{a} {s}] FAILED {type(e).__name__}: {e}")
                failures.append((a, s, str(e)[:200]))
    if failures:
        raise SystemExit(f"{len(failures)} roofline failures: {failures}")


if __name__ == "__main__":
    main()
