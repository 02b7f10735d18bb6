import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first initialization, and the production meshes below need 256/512
# placeholder host devices.  (Only the dry-run sets this — tests/benches see
# the real single device.)

"""Multi-pod dry-run (deliverable e) + roofline raw-term extraction (g).

For every (architecture x input-shape x mesh) cell this lowers + compiles the
real step function under the production mesh, proving the distribution config
is coherent:

  train_4k    -> train_step  (fwd+bwd+AdamW update, donated params/opt)
  prefill_32k -> prefill     (cache build + last logits)
  decode_32k  -> serve_step  (one token over a 32k KV cache, donated cache)
  long_500k   -> serve_step  (SSM/hybrid archs only; see DESIGN.md)

and records memory_analysis() + cost_analysis() + a collective-bytes parse of
the partitioned HLO into a JSON artifact per cell.

FLOP-accounting correction (EXPERIMENTS.md §Roofline): XLA's HloCostAnalysis
counts a while-loop body ONCE, so the scanned-over-layers full-model numbers
undercount by ~n_layers.  Each cell therefore ALSO lowers the per-layer step
(inner chunk loops unrolled) and the embed/head "outer" step separately, and
reports   total = outer + n_layers * layer   (RWKV's time scan is unrolled at
a reduced S and scaled linearly — every RWKV6 op is linear in S).
"""
import argparse
import json
import math
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.sharding import (RULE_SETS, batch_sharding, replicated,
                            set_current_mesh, sharding_tree, spec_for)
from repro.train.optim import AdamWConfig, abstract_opt_state, adamw_update, opt_state_axes

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")
_COLL_RE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                      r"collective-permute)(-start)?\(")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> dict:
    """Approximate bytes moved per device per collective op (result-shape
    based; all-reduce counted 2x = reduce-scatter + all-gather of a ring)."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for line in hlo_text.splitlines():
        mm = _COLL_RE.search(line)
        if not mm or "-done" in line:
            continue
        kind = mm.group(1)
        eq = line.find(" = ")
        if eq < 0:
            continue
        # result type region: between " = " and the op name (handles tuple
        # results of async -start variants)
        region = line[eq + 3:mm.start()]
        size = sum(_shape_bytes(s) for s in _SHAPE_RE.finditer(region))
        if kind in ("all-gather", "all-reduce") and mm.group(2):
            # async start ops carry (input, output) tuples — count output only
            size = size // 2
        factor = 2 if kind == "all-reduce" else 1
        out[kind] += size * factor
        out["count"] += 1
    out["total"] = sum(v for k, v in out.items() if k not in ("count", "total"))
    return out


def cost_summary(compiled) -> dict:
    ca = compiled.cost_analysis() or {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def mem_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------
def _axes_to_shardings(mesh, axes_tree, shapes_tree, rules, fallbacks=None):
    return sharding_tree(mesh, axes_tree, shapes_tree, rules, fallbacks)


def lower_full(cfg: ModelConfig, shape_name: str, mesh, rules: str):
    """Lower + compile the full step function for the cell.  Returns
    (compiled, lowered, fallbacks)."""
    shape = SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)
    fallbacks: list = []
    params = M.abstract_params(cfg)
    p_shard = _axes_to_shardings(mesh, M.param_axes(cfg), params, rules, fallbacks)
    b_shard = batch_sharding(mesh, specs, rules)
    ocfg = AdamWConfig()

    if shape.kind == "train":
        opt = abstract_opt_state(params)
        o_shard = {"m": p_shard, "v": p_shard, "step": replicated(mesh)}

        def train_step(p, o, b):
            loss, grads = jax.value_and_grad(lambda pp: M.loss_fn(pp, b, cfg))(p)
            new_p, new_o, metrics = adamw_update(grads, p, o, ocfg)
            return loss, new_p, new_o

        jitted = jax.jit(
            train_step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(replicated(mesh), p_shard, o_shard),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params, opt, specs)
    elif shape.kind == "prefill":
        def prefill_step(p, b):
            return M.prefill(p, b, cfg, max_len=shape.seq_len)

        cache_shapes = jax.eval_shape(
            lambda: M.make_cache(cfg, shape.global_batch, shape.seq_len))
        c_shard = _axes_to_shardings(mesh, M.cache_axes(cfg), cache_shapes,
                                     rules, fallbacks)
        logits_shard = NamedSharding(
            mesh, spec_for(mesh, ("batch", None, None),
                           (shape.global_batch, 1, cfg.vocab), RULE_SETS[rules]))
        jitted = jax.jit(prefill_step, in_shardings=(p_shard, b_shard),
                         out_shardings=(c_shard, logits_shard))
        lowered = jitted.lower(params, specs)
    else:  # decode
        cache_shapes = jax.eval_shape(
            lambda: M.make_cache(cfg, shape.global_batch, shape.seq_len))
        c_shard = _axes_to_shardings(mesh, M.cache_axes(cfg), cache_shapes,
                                     rules, fallbacks)
        logits_shard = NamedSharding(
            mesh, spec_for(mesh, ("batch", None, None),
                           (shape.global_batch, 1, cfg.vocab), RULE_SETS[rules]))

        def serve_step(p, c, b):
            return M.decode_step(p, c, b, cfg)

        jitted = jax.jit(serve_step,
                         in_shardings=(p_shard, c_shard, b_shard),
                         out_shardings=(logits_shard, c_shard),
                         donate_argnums=(1,))
        lowered = jitted.lower(params, cache_shapes, specs)
    t0 = time.time()
    compiled = lowered.compile()
    return compiled, lowered, fallbacks, time.time() - t0


# ---------------------------------------------------------------------------
# Per-layer accounting (FLOP-exact decomposition)
# ---------------------------------------------------------------------------
def _layer_abstract(cfg: ModelConfig):
    """One layer's abstract params + axes (no leading 'layers' dim)."""
    specs = M.layer_specs(cfg)
    shapes = M._nest({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in specs.items()})
    axes = M._nest({k: v.axes for k, v in specs.items()})
    return shapes, axes


def _shared_abstract(cfg: ModelConfig):
    specs = {k[len("shared/"):]: v for k, v in M.model_specs(cfg).items()
             if k.startswith("shared/")}
    if not specs:
        return None, None
    shapes = M._nest({k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                      for k, v in specs.items()})
    axes = M._nest({k: v.axes for k, v in specs.items()})
    return shapes, axes


def _acct(lowered) -> dict:
    compiled = lowered.compile()
    c = cost_summary(compiled)
    c["collectives"] = collective_bytes(compiled.as_text())
    return c


def account_cell(cfg: ModelConfig, shape_name: str, mesh, rules: str,
                 flash: bool = False) -> dict:
    """Exact-FLOP decomposition: outer + n_layers x layer (+ zamba shared).
    ``flash``: lower attention as a kernel stub and add the Pallas kernel's
    analytic costs (EXPERIMENTS.md §Perf H3)."""
    shape = SHAPES[shape_name]
    if flash:
        cfg = cfg.replace(attn_impl="kernel_stub")
    B, S = shape.global_batch, shape.seq_len
    rule = RULE_SETS[rules]
    out: dict = {"n_layers": cfg.n_layers}

    lp_shapes, lp_axes = _layer_abstract(cfg)
    lp_shard = _axes_to_shardings(mesh, lp_axes, lp_shapes, rules)
    x_sds = jax.ShapeDtypeStruct((B, S if shape.kind != "decode" else 1,
                                  cfg.d_model), jnp.bfloat16)
    x_shard = NamedSharding(mesh, spec_for(mesh, ("batch", None, None),
                                           x_sds.shape, rule))
    if cfg.mrope:
        pos_sds = jax.ShapeDtypeStruct((B, x_sds.shape[1], 3), jnp.int32)
    else:
        pos_sds = jax.ShapeDtypeStruct((B, x_sds.shape[1]), jnp.int32)
    pos_shard = NamedSharding(mesh, spec_for(mesh, ("batch",) + (None,) * (len(pos_sds.shape) - 1),
                                             pos_sds.shape, rule))

    # RWKV's time scan is unrolled at a reduced S and scaled (all ops linear)
    s_acc, scale = (S, 1.0)
    if cfg.rwkv and shape.kind != "decode":
        s_acc = min(S, 256)
        scale = S / s_acc
        x_sds = jax.ShapeDtypeStruct((B, s_acc, cfg.d_model), jnp.bfloat16)
        pos_sds = jax.ShapeDtypeStruct((B, s_acc), jnp.int32)

    if shape.kind in ("train", "prefill"):
        def layer_fwd(lp, x, pos):
            y, aux = M.layer_step(lp, x, pos, jnp.int32(0), cfg, unroll=True)
            return y

        if shape.kind == "train":
            def layer_train(lp, x, pos):
                f = layer_fwd
                if cfg.remat == "block":
                    f = jax.checkpoint(f)
                y = f(lp, x, pos)
                # bf16 sum: the real inter-layer cotangent is the bf16
                # residual stream, so grads/collectives stay bf16-sized
                return jnp.sum(y)

            g = jax.value_and_grad(layer_train, argnums=(0, 1))
            low = jax.jit(g, in_shardings=(lp_shard, x_shard, pos_shard)
                          ).lower(lp_shapes, x_sds, pos_sds)
        else:
            low = jax.jit(layer_fwd, in_shardings=(lp_shard, x_shard, pos_shard)
                          ).lower(lp_shapes, x_sds, pos_sds)
        out["layer"] = _acct(low)
        out["layer_scale"] = scale

        # zamba2: the shared attention(+MLP) block runs n_shared times and is
        # NOT inside the per-layer cost (layer_step's cond skips it when
        # shared=None) — account it separately at full S (it is quadratic).
        if cfg.attn_every:
            sh_shapes, sh_axes = _shared_abstract(cfg)
            sh_shard = _axes_to_shardings(mesh, sh_axes, sh_shapes, rules)
            x_full = jax.ShapeDtypeStruct((B, S if shape.kind != "decode" else 1,
                                           cfg.d_model), jnp.bfloat16)
            xf_shard = NamedSharding(mesh, spec_for(mesh, ("batch", None, None),
                                                    x_full.shape, rule))
            pos_full = jax.ShapeDtypeStruct((B, x_full.shape[1]), jnp.int32)
            pf_shard = NamedSharding(mesh, spec_for(mesh, ("batch", None),
                                                    pos_full.shape, rule))

            def shared_fwd(sp, x, pos):
                from repro.models.layers import (attention_block, mlp_block,
                                                 rmsnorm)
                cat = jnp.concatenate([x, x], axis=-1)
                h = rmsnorm(cat, sp["ln_in"]["scale"], cfg.norm_eps)
                a = attention_block(h, sp["attn"], cfg, pos, unroll=True)
                xx = x + a
                h2 = rmsnorm(xx, sp["ln_mlp"]["scale"], cfg.norm_eps)
                return xx + mlp_block(h2, sp["mlp"], cfg)

            if shape.kind == "train":
                gsh = jax.value_and_grad(
                    lambda sp, x, pos: jnp.sum(shared_fwd(sp, x, pos)),
                    argnums=(0, 1))
                low = jax.jit(gsh, in_shardings=(sh_shard, xf_shard, pf_shard)
                              ).lower(sh_shapes, x_full, pos_full)
            else:
                low = jax.jit(shared_fwd, in_shardings=(sh_shard, xf_shard, pf_shard)
                              ).lower(sh_shapes, x_full, pos_full)
            out["shared"] = _acct(low)
            out["n_shared"] = cfg.n_shared_attn

        # outer: embedding + head + loss (train) / head only (prefill)
        specs = input_specs(cfg, shape_name)
        b_shard = batch_sharding(mesh, specs, rules)
        pe = jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), jnp.bfloat16)
        ph = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab), jnp.bfloat16)
        pn = jax.ShapeDtypeStruct((cfg.d_model,), jnp.bfloat16)
        pe_sh = NamedSharding(mesh, spec_for(mesh, ("vocab", "embed"), pe.shape, rule))
        ph_sh = NamedSharding(mesh, spec_for(mesh, ("embed", "vocab"), ph.shape, rule))
        pn_sh = replicated(mesh)

        def outer_fn(pe_, ph_, pn_, b):
            prm = {"embed": {"table": pe_}, "final_norm": {"scale": pn_},
                   "lm_head": {"w": ph_}}
            x, _ = M._embed_inputs(prm, b, cfg)
            logits = M._logits(prm, x, cfg)
            if shape.kind == "train":
                targets = b["targets"]
                mask = (targets >= 0).astype(jnp.float32)
                t = jnp.clip(targets, 0)
                logz = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
                return ((logz - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
            return jnp.sum(logits[:, -1].astype(jnp.float32))

        if shape.kind == "train":
            gout = jax.value_and_grad(outer_fn, argnums=(0, 1, 2))
            low = jax.jit(gout, in_shardings=(pe_sh, ph_sh, pn_sh, b_shard)
                          ).lower(pe, ph, pn, specs)
        else:
            low = jax.jit(outer_fn, in_shardings=(pe_sh, ph_sh, pn_sh, b_shard)
                          ).lower(pe, ph, pn, specs)
        out["outer"] = _acct(low)

        # AdamW update flops (train): elementwise over params — analytic
        if shape.kind == "train":
            out["optimizer_flops_analytic"] = 14.0 * M.n_params(cfg) / mesh.size
        if flash:
            out["flash_kernel"] = flash_kernel_costs(cfg, shape_name, mesh.size)
        return out

    # ---- decode accounting ----
    cache_shapes = jax.eval_shape(lambda: M.make_cache(cfg, B, S))
    c_axes = M.cache_axes(cfg)
    length = jax.ShapeDtypeStruct((), jnp.int32)

    if cfg.rwkv:
        def dec_layer(lp, x, wkv, tm, cm):
            lc = {"wkv": wkv, "tm_x": tm, "cm_x": cm}
            y, nc = M.decode_layer_step(lp, x, cfg, lc, jnp.int32(0),
                                        jnp.zeros((B, 1), jnp.int32), jnp.int32(0))
            return y, nc

        wkv = jax.ShapeDtypeStruct(cache_shapes["wkv"].shape[1:], jnp.float32)
        tm = jax.ShapeDtypeStruct(cache_shapes["tm_x"].shape[1:], jnp.bfloat16)
        cm = jax.ShapeDtypeStruct(cache_shapes["cm_x"].shape[1:], jnp.bfloat16)
        shard_of = lambda ax, sds: NamedSharding(mesh, spec_for(mesh, ax, sds.shape, rule))
        low = jax.jit(dec_layer, in_shardings=(
            lp_shard, x_shard,
            shard_of(("batch", "ssm_heads", None, None), wkv),
            shard_of(("batch", None, None), tm),
            shard_of(("batch", None, None), cm)),
            donate_argnums=(2, 3, 4),
        ).lower(lp_shapes, x_sds, wkv, tm, cm)
        out["layer"] = _acct(low)
        out["layer_scale"] = 1.0
    elif cfg.family == "hybrid":
        from repro.models.ssm import mamba2_decode_step

        def dec_layer(lp, x, ssm, conv):
            h = x  # norm negligible
            return mamba2_decode_step(h, lp["mamba"], cfg, ssm, conv)

        ssm = jax.ShapeDtypeStruct(cache_shapes["ssm"].shape[1:], jnp.float32)
        conv = jax.ShapeDtypeStruct(cache_shapes["conv"].shape[1:], jnp.bfloat16)
        shard_of = lambda ax, sds: NamedSharding(mesh, spec_for(mesh, ax, sds.shape, rule))
        low = jax.jit(dec_layer, in_shardings=(
            lp_shard, x_shard,
            shard_of(("batch", "ssm_heads", None, None), ssm),
            shard_of(("batch", None, None), conv)),
            donate_argnums=(2, 3),
        ).lower(lp_shapes, x_sds, ssm, conv)
        out["layer"] = _acct(low)
        out["layer_scale"] = 1.0

        # shared attention decode over the full cache
        sh_shapes, sh_axes = _shared_abstract(cfg)
        sh_shard = _axes_to_shardings(mesh, sh_axes, sh_shapes, rules)
        kc = jax.ShapeDtypeStruct(cache_shapes["k"].shape[1:], jnp.bfloat16)
        vc = jax.ShapeDtypeStruct(cache_shapes["v"].shape[1:], jnp.bfloat16)
        kc_sh = shard_of(("batch", None, "kv_cache_heads", None), kc)

        def dec_shared(sp, x, k, v):
            from repro.models.layers import (attention_decode_block, mlp_block,
                                             rmsnorm)
            cat = jnp.concatenate([x, x], axis=-1)
            h = rmsnorm(cat, sp["ln_in"]["scale"], cfg.norm_eps)
            a, k, v = attention_decode_block(h, sp["attn"], cfg,
                                             jnp.zeros((B, 1), jnp.int32), k, v,
                                             jnp.int32(S - 1))
            xx = x + a
            h2 = rmsnorm(xx, sp["ln_mlp"]["scale"], cfg.norm_eps)
            return xx + mlp_block(h2, sp["mlp"], cfg), k, v

        low = jax.jit(dec_shared, in_shardings=(sh_shard, x_shard, kc_sh, kc_sh),
                      donate_argnums=(2, 3)).lower(sh_shapes, x_sds, kc, vc)
        out["shared"] = _acct(low)
        out["n_shared"] = cfg.n_shared_attn
    else:
        def dec_layer(lp, x, *cache_leaves):
            keys = ["k", "v"] + (["k_scale", "v_scale"] if cfg.kv_quant else [])
            lc = dict(zip(keys, cache_leaves))
            if cfg.mrope:
                pos = jnp.full((B, 1, 3), S - 1, jnp.int32)
            else:
                pos = jnp.full((B, 1), S - 1, jnp.int32)
            y, nc = M.decode_layer_step(lp, x, cfg, lc, jnp.int32(S - 1),
                                        pos, jnp.int32(0))
            return y, nc

        kc = jax.ShapeDtypeStruct(cache_shapes["k"].shape[1:],
                                  cache_shapes["k"].dtype)
        kc_sh = NamedSharding(mesh, spec_for(
            mesh, ("batch", None, "kv_cache_heads", None), kc.shape, rule))
        leaves = [kc, kc]
        shards = [kc_sh, kc_sh]
        if cfg.kv_quant:
            sc = jax.ShapeDtypeStruct(cache_shapes["k_scale"].shape[1:],
                                      jnp.bfloat16)
            sc_sh = NamedSharding(mesh, spec_for(
                mesh, ("batch", None, "kv_cache_heads"), sc.shape, rule))
            leaves += [sc, sc]
            shards += [sc_sh, sc_sh]
        low = jax.jit(dec_layer, in_shardings=tuple([lp_shard, x_shard] + shards),
                      donate_argnums=tuple(range(2, 2 + len(leaves)))
                      ).lower(lp_shapes, x_sds, *leaves)
        out["layer"] = _acct(low)
        out["layer_scale"] = 1.0

    # outer decode: embed row + head matmul
    pe = jax.ShapeDtypeStruct((cfg.vocab, cfg.d_model), jnp.bfloat16)
    ph = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab), jnp.bfloat16)
    pn = jax.ShapeDtypeStruct((cfg.d_model,), jnp.bfloat16)
    pe_sh = NamedSharding(mesh, spec_for(mesh, ("vocab", "embed"), pe.shape, rule))
    ph_sh = NamedSharding(mesh, spec_for(mesh, ("embed", "vocab"), ph.shape, rule))
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tk_sh = NamedSharding(mesh, spec_for(mesh, ("batch", None), toks.shape, rule))

    def outer_dec(pe_, ph_, pn_, t):
        prm = {"embed": {"table": pe_}, "final_norm": {"scale": pn_},
               "lm_head": {"w": ph_}}
        x = pe_[t]
        return M._logits(prm, x, cfg)

    low = jax.jit(outer_dec, in_shardings=(pe_sh, ph_sh, replicated(mesh), tk_sh)
                  ).lower(pe, ph, pn, toks)
    out["outer"] = _acct(low)
    return out


# ---------------------------------------------------------------------------
# Analytic reference (MODEL_FLOPS)
# ---------------------------------------------------------------------------
def flash_kernel_costs(cfg: ModelConfig, shape_name: str, n_dev: int) -> dict:
    """Analytic per-device cost of the Pallas flash-attention kernel for one
    step: FLOPs = 2 matmuls over the causal triangle (x3.5 for train: fwd +
    bwd incl. recompute); HBM bytes = q/k/v read + o written (x2.5 train).
    Scores/probabilities live in VMEM (that is the point of the kernel)."""
    shape = SHAPES[shape_name]
    if shape.kind == "decode" or cfg.n_heads == 0:
        return {"flops": 0.0, "bytes": 0.0}
    S, B = shape.seq_len, shape.global_batch
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    n_attn = cfg.n_shared_attn if cfg.family == "hybrid" else cfg.n_layers
    flops = 2 * 2 * B * H * hd * (S * S / 2.0)          # QK^T + PV, causal
    bytes_ = 2 * B * S * hd * (2 * H + 2 * K)           # q,o (H) + k,v (K) bf16
    mult_f = 3.5 if shape.kind == "train" else 1.0
    mult_b = 2.5 if shape.kind == "train" else 1.0
    return {"flops": flops * n_attn * mult_f / n_dev,
            "bytes": bytes_ * n_attn * mult_b / n_dev}


def attn_score_hbm_bytes(cfg: ModelConfig, shape_name: str, n_dev: int) -> float:
    """Per-device HBM bytes the jnp chunked-attention stand-in spends on the
    (cq x ck) score/probability blocks per step.  The Pallas flash kernel
    (kernels/flash_attention) keeps these in VMEM, so the TPU deployment's
    memory term subtracts them (documented in EXPERIMENTS.md §Perf).
    Counted as ~3 f32 traversals (scores out, exp in/out) of the triangular
    S^2/2 block area per layer, q-heads wide."""
    shape = SHAPES[shape_name]
    if shape.kind == "decode" or cfg.n_heads == 0:
        return 0.0
    S, B = shape.seq_len, shape.global_batch
    per_layer = 3.0 * 4.0 * B * cfg.n_heads * (S * S / 2.0)
    n_attn_layers = cfg.n_shared_attn if cfg.family == "hybrid" else cfg.n_layers
    mult = 3.0 if shape.kind == "train" else 1.0   # fwd + bwd recompute
    return per_layer * n_attn_layers * mult / n_dev


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE) for train; 2*N*D for inference
    fwd; decode D = batch tokens (1 per seq)."""
    shape = SHAPES[shape_name]
    n_active = M.n_active_params(cfg)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, multi_pod: bool, rules: str,
             out_dir: Path, skip_accounting: bool = False,
             kv_quant: bool = False, flash: bool = False,
             moe_a2a: bool = False) -> dict:
    cfg = get(arch)
    if kv_quant:
        cfg = cfg.replace(kv_quant=True)
    if moe_a2a:
        cfg = cfg.replace(moe_impl="a2a")
    if SHAPES[shape_name].seq_len >= 32768 and not cfg.rwkv:
        # larger chunks at long S keep the unrolled accounting HLO small
        cfg = cfg.replace(attn_chunk_q=2048, attn_chunk_k=2048)
    skip = shape_applicable(cfg, shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "rules": rules, "ts": time.time()}
    if skip:
        rec["status"] = skip
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_current_mesh(mesh, rules)   # model-level sharding constraints (MoE EP)
    t0 = time.time()
    compiled, lowered, fallbacks, compile_s = lower_full(cfg, shape_name, mesh, rules)
    rec.update(
        status="ok",
        n_devices=mesh.size,
        compile_seconds=compile_s,
        lower_seconds=time.time() - t0 - compile_s,
        memory=mem_summary(compiled),
        full_cost=cost_summary(compiled),
        full_collectives=collective_bytes(compiled.as_text()),
        sharding_fallbacks=[f"{n}:dim{d}%{e}" for n, s, d, e in fallbacks],
        model_flops=model_flops(cfg, shape_name),
        attn_score_hbm_bytes=attn_score_hbm_bytes(cfg, shape_name, mesh.size),
        n_params=M.n_params(cfg),
        n_active_params=M.n_active_params(cfg),
    )
    if not skip_accounting and not multi_pod:
        rec["accounting"] = account_cell(cfg, shape_name, mesh, rules,
                                         flash=flash)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default="fsdp_tp")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-accounting", action="store_true")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--flash", action="store_true")
    ap.add_argument("--moe-a2a", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else [a for a in ARCHS if a != "paper-scorer"]
    shapes = [args.shape] if args.shape else list(SHAPES)
    for arch in archs:
        for shape in shapes:
            tag = (f"{arch}__{shape}__"
                   f"{'pod2x16x16' if args.multi_pod else 'pod16x16'}__"
                   f"{args.rules}{args.tag}")
            path = out_dir / f"{tag}.json"
            if path.exists():
                print(f"[skip cached] {tag}")
                continue
            t0 = time.time()
            try:
                rec = run_cell(arch, shape, args.multi_pod, args.rules, out_dir,
                               args.skip_accounting, kv_quant=args.kv_quant,
                               flash=args.flash, moe_a2a=args.moe_a2a)
            except Exception as e:  # noqa: BLE001 — record the failure
                import traceback
                rec = {"arch": arch, "shape": shape, "rules": args.rules,
                       "mesh": "pod2x16x16" if args.multi_pod else "pod16x16",
                       "status": f"FAILED: {type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
            rec["wall_seconds"] = time.time() - t0
            path.write_text(json.dumps(rec, indent=1))
            print(f"[{rec.get('status', '?')[:60]:60s}] {tag} ({rec['wall_seconds']:.0f}s)")


if __name__ == "__main__":
    main()
