import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces the compiled artifact's

* ``memory_analysis()``  -- per-device bytes (proves what fits),
* ``cost_analysis()``    -- per-device FLOPs / bytes accessed,
* collective traffic parsed from the post-SPMD HLO text,

and corrects lax.scan once-counting with **per-segment extrapolation**:
XLA counts a scanned layer body once, so we additionally compile small
*unrolled* variants (all segments at repeat=1; each segment at repeat=2)
and linearly extrapolate  true = c1 + sum_s (rep_s - 1) * (c_s - c1),
which is exact because every program here is layer-linear.  Memory numbers
come from the full scanned compile (the shipped program).

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
    python -m repro.launch.dryrun ... --strategy <name>   # perf hillclimb
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_config, input_specs, skip_reason, SHAPES
from repro.launch.hlo_analysis import collective_traffic
from repro.launch.mesh import make_production_mesh, v5e_constants
from repro.models import model as M
from repro.models.config import ModelConfig, segment_layers
from repro.models.params import abstract_params, partition_specs
from repro.serving.steps import make_decode_step, make_prefill_step
from repro.training import OptConfig, make_train_step
from repro.training.optimizer import opt_init
from repro.training.sharding import auto_demote, batch_spec, make_rules

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


# --------------------------------------------------------------- strategies
#
# A strategy is a named set of sharding / step-construction choices; the
# perf loop (EXPERIMENTS.md section Perf) iterates over these.

STRATEGIES: dict[str, dict] = {
    # paper-faithful baseline: TP over "model", FSDP over "data" for train,
    # serving caches sharded (batch -> data, seq -> model).
    "baseline": {},
    # decode: shard the KV cache over kv_heads instead of seq
    "kv_heads": {"cache_seq_axis": None, "cache_heads_axis": "model"},
    # decode: int8 KV cache with per-(token, head) scales (~2x less HBM)
    "kv_int8": {"kv_quant": True},
    # train: no remat (more memory, fewer FLOPs) -- ablation point
    "no_remat": {"remat": False},
    # train: 2D sharded batch (batch over data+model) for giant-batch cells
    "batch_2d": {"batch_over_model": True},
    # moe: expert parallelism over the whole pod (1 expert-shard per chip)
    "expert_ep": {"moe_expert_axis": ("data", "model")},
    # moe: align the dispatch buffer's capacity dim with the token axis so
    # the scatter stays local (kills GSPMD's buffer-sized all-reduces)
    "moe_dispatch": {"moe_dispatch_hint": ("model", "data")},
    # combined serving fix for giant MoEs: pod-wide EP + local dispatch
    "ep_dispatch": {"moe_expert_axis": ("data", "model"),
                    "moe_dispatch_hint": (("data", "model"), None)},
    # combined: pod-wide EP + local dispatch + int8 latent/KV cache
    "ep_dispatch_int8": {"moe_expert_axis": ("data", "model"),
                         "moe_dispatch_hint": (("data", "model"), None),
                         "kv_quant": True},
    # small-model training: pure 256-way data parallelism (params
    # replicated).  Constraining only the *inputs* to a 2D batch is not
    # enough -- GSPMD re-shards activations to match FSDP/TP weight
    # layouts -- so this also replicates every weight rule.
    "dp_all": {"replicate_params": True, "batch_over_model": True},
    # moe: tighter capacity factor (1.05): ~16% less dispatch-buffer
    # traffic at the cost of a little token dropping under skew
    "moe_cap105": {"moe_dispatch_hint": ("model", "data"),
                   "moe_capacity": 1.05},
    # few-expert MoE serving fit: shard the expert FF dim over the whole
    # pod (grok-1: 8 experts can't split 256 ways, but d_ff=32768 can)
    "ff_pod": {"moe_expert_ff_axis": ("data", "model")},
}


# ------------------------------------------------------------- shardings


def _dtype_of(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def build_rules(cfg: ModelConfig, mesh, kind: str, strategy: dict) -> dict:
    if strategy.get("replicate_params"):
        from repro.models.params import DEFAULT_RULES
        return {k: None for k in DEFAULT_RULES}
    fsdp = kind == "train"
    fsdp_axis = ("pod", "data") if ("pod" in mesh.axis_names and fsdp) else "data"
    overrides = {}
    if strategy.get("moe_expert_axis"):
        overrides["expert"] = strategy["moe_expert_axis"]
    if strategy.get("moe_expert_ff_axis"):
        overrides["expert"] = None
        overrides["expert_ff"] = strategy["moe_expert_ff_axis"]
    rules = make_rules(mesh, fsdp=fsdp, fsdp_axis=fsdp_axis,
                       overrides=overrides)
    defs = M.model_defs(cfg)
    rules = auto_demote(defs, rules, mesh)
    if (cfg.moe is not None and rules.get("expert") is None
            and rules.get("expert_ff") is None):
        # few-expert MoE (e.g. grok's 8 experts < 16-way axis): fall back to
        # tensor-parallel experts -- shard the expert FF dim instead of the
        # expert dim, so expert weights never replicate across the pod.
        trial = dict(rules)
        trial["expert_ff"] = "model"
        trial2 = auto_demote(defs, trial, mesh)
        if trial2.get("expert_ff") == "model":
            rules = trial2
    return rules


def cache_pspecs(cfg: ModelConfig, caches_abs, mesh, strategy: dict,
                 batch_axis="data"):
    """PartitionSpecs for the (segment-stacked) cache tree.

    Leaves are (layer_rep, B, ...): batch -> "data"; the sequence dim of
    attention/MLA caches -> "model" (baseline) so long KV shards; SSM/LRU
    states replicate over "model" unless head-divisible.
    """
    seq_ax = strategy.get("cache_seq_axis", "model")
    heads_ax = strategy.get("cache_heads_axis", None)
    msize = mesh.shape["model"]
    bsize = (int(np.prod([mesh.shape[a] for a in batch_axis]))
             if isinstance(batch_axis, tuple)
             else (mesh.shape[batch_axis] if batch_axis else 1))

    def spec_for(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        ax = [None] * nd
        ax[1] = batch_axis if (batch_axis and
                               leaf.shape[1] % bsize == 0) else None
        if name in ("k", "v", "xk", "xv"):
            # k, v: (rep, B, S, KV·D); xk, xv: (rep, B, S, KV, D)
            if heads_ax and cfg.attn.n_kv_heads % msize == 0:
                ax[3] = heads_ax
            elif seq_ax and leaf.shape[2] % msize == 0:
                ax[2] = seq_ax
        elif name in ("c_kv", "k_rope", "k_s", "v_s"):
            if seq_ax and leaf.shape[2] % msize == 0:
                ax[2] = seq_ax
        elif name in ("pos", "c_s", "r_s"):
            if seq_ax and leaf.shape[2] % msize == 0:
                ax[2] = seq_ax
        elif name == "ssm":  # (rep, B, H, P, N)
            if leaf.shape[2] % msize == 0:
                ax[2] = "model"
        elif name == "h":  # (rep, B, W)
            if leaf.shape[2] % msize == 0:
                ax[2] = "model"
        # conv caches replicate over model
        return P(*ax)

    return jax.tree_util.tree_map_with_path(spec_for, caches_abs)


def _ns(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------- cell builds


def _abstract_opt(params_abs, ocfg):
    return jax.eval_shape(lambda p: opt_init(p, ocfg), params_abs)


def _abstract_cache(cfg, batch, max_len, dtype):
    return jax.eval_shape(
        lambda: M.init_cache(cfg, batch, max_len, dtype))


def lower_cell(cfg: ModelConfig, shape_name: str, mesh, *, unroll: bool,
               strategy: dict, donate: bool = True):
    """Lower one (cfg, shape) on mesh; returns jax ``Lowered``."""
    kind = SHAPES[shape_name].kind
    sspec = SHAPES[shape_name]
    if strategy.get("kv_quant"):
        cfg = cfg.replace(kv_quant=True)
    if strategy.get("moe_dispatch_hint") and cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, dispatch_hint=strategy["moe_dispatch_hint"]))
    if strategy.get("moe_capacity") and cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=strategy["moe_capacity"]))
    dtype = _dtype_of(cfg)
    defs = M.model_defs(cfg)
    rules = build_rules(cfg, mesh, kind, strategy)
    pspecs = partition_specs(defs, rules)
    params_abs = abstract_params(defs, dtype)
    params_sh = _ns(mesh, pspecs)
    bspec = batch_spec(mesh) if not strategy.get("batch_over_model") else P(
        tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names))
    gb = SHAPES[shape_name].global_batch
    baxes = bspec[0] if bspec else ()
    bsize = int(np.prod([mesh.shape[a] for a in (
        baxes if isinstance(baxes, tuple) else (baxes,))])) if baxes else 1
    if gb % bsize != 0:
        bspec = P()  # e.g. long_500k's global_batch=1: replicate the batch
    data_sh = NamedSharding(mesh, bspec)
    repl = NamedSharding(mesh, P())

    specs = input_specs(cfg, shape_name)

    if kind == "train":
        big = M.param_count(cfg) > 100e9
        ocfg = OptConfig(state_dtype="bfloat16" if big else "float32")
        opt_abs = _abstract_opt(params_abs, ocfg)
        opt_sh = {"m": params_sh, "v": params_sh, "step": repl}
        state_abs = {"params": params_abs, "opt": opt_abs}
        state_sh = {"params": params_sh, "opt": opt_sh}
        step = make_train_step(cfg, ocfg, microbatches=1,
                               remat=strategy.get("remat", True),
                               unroll=unroll)
        batch_abs = dict(specs)
        batch_sh = {k: data_sh for k in batch_abs}
        fn = jax.jit(step,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, repl),
                     donate_argnums=(0,) if donate else ())
        with jax.set_mesh(mesh):
            return fn.lower(state_abs, batch_abs)

    batch_axis = bspec[0] if len(bspec) else None

    if kind == "prefill":
        pstep = make_prefill_step(cfg, unroll=unroll)
        gb, S = specs["tokens"].shape
        extra = cfg.vision.n_patches if cfg.vision is not None else 0
        caches_abs = _abstract_cache(cfg, gb, S + extra, dtype)
        cache_sh = _ns(mesh, cache_pspecs(cfg, caches_abs, mesh, strategy,
                                          batch_axis))
        stub_keys = [k for k in specs if k in ("enc_frames", "prefix_embeds")]

        def fn(params, caches, tokens, positions, *stubs):
            kw = dict(zip(stub_keys, stubs))
            return pstep(params, caches, tokens, positions, **kw)

        jfn = jax.jit(
            fn,
            in_shardings=(params_sh, cache_sh, data_sh, data_sh,
                          *([data_sh] * len(stub_keys))),
            out_shardings=(cache_sh, data_sh),
            donate_argnums=(1,) if donate else ())
        with jax.set_mesh(mesh):
            return jfn.lower(params_abs, caches_abs, specs["tokens"],
                             specs["positions"],
                             *[specs[k] for k in stub_keys])

    # decode
    dstep = make_decode_step(cfg, unroll=unroll, masked=False)
    gb = specs["tokens"].shape[0]
    S = sspec.seq_len
    caches_abs = _abstract_cache(cfg, gb, S, dtype)
    cache_sh = _ns(mesh, cache_pspecs(cfg, caches_abs, mesh, strategy,
                                      batch_axis))
    vec_sh = data_sh
    state_abs = {
        "caches": caches_abs,
        "length": jax.ShapeDtypeStruct((gb,), jnp.int32),
        "last_token": jax.ShapeDtypeStruct((gb,), jnp.int32),
        "active": jax.ShapeDtypeStruct((gb,), jnp.bool_),
    }
    state_sh = {"caches": cache_sh, "length": vec_sh, "last_token": vec_sh,
                "active": vec_sh}
    jfn = jax.jit(
        dstep,
        in_shardings=(params_sh, state_sh),
        out_shardings=(state_sh, vec_sh),
        donate_argnums=(1,) if donate else ())
    with jax.set_mesh(mesh):
        return jfn.lower(params_abs, state_abs)


# ----------------------------------------------------------- extrapolation


def make_variant(cfg: ModelConfig, seg_reps, enc_layers=None) -> ModelConfig:
    segs = segment_layers(cfg.block_specs())
    blocks: list = []
    for (block, _rep), r in zip(segs, seg_reps):
        blocks += list(block) * r
    out = cfg.replace(blocks_override=tuple(blocks), n_layers=len(blocks))
    if cfg.encoder is not None and enc_layers is not None:
        out = out.replace(
            encoder=dataclasses.replace(cfg.encoder, n_layers=enc_layers))
    return out


def _analyze(lowered) -> dict:
    comp = lowered.compile()
    ca = comp.cost_analysis() or {}
    coll = collective_traffic(comp.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": coll,
        "_compiled": comp,
    }


def analyze_cell(cfg: ModelConfig, shape_name: str, mesh, *,
                 strategy: dict, variants: bool = True) -> dict:
    """Full analysis: scanned compile (memory) + extrapolated costs."""
    t0 = time.time()
    full_low = lower_cell(cfg, shape_name, mesh, unroll=False,
                          strategy=strategy)
    full = _analyze(full_low)
    mem = full["_compiled"].memory_analysis()
    out = {
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
        },
        "scanned": {k: full[k] for k in ("flops", "bytes")},
        "scanned_coll": full["coll"],
    }

    segs = segment_layers(cfg.block_specs())
    reps = [r for _, r in segs]
    enc_L = cfg.encoder.n_layers if cfg.encoder is not None else None
    if not variants or (all(r == 1 for r in reps) and (enc_L or 1) == 1):
        out["extrapolated"] = {
            "flops": full["flops"], "bytes": full["bytes"],
            "coll_total": full["coll"]["total"],
            "coll": {k: v for k, v in full["coll"].items()
                     if k != "counts"},
        }
        out["compile_seconds"] = time.time() - t0
        return out

    def cost_of(seg_reps, enc):
        v = make_variant(cfg, seg_reps, enc)
        low = lower_cell(v, shape_name, mesh, unroll=True, strategy=strategy,
                         donate=False)
        return _analyze(low)

    ones = [1] * len(segs)
    c1 = cost_of(ones, 1 if enc_L else None)
    terms = []  # (multiplier, cost_dict)
    for si in range(len(segs)):
        if reps[si] == 1:
            continue
        r2 = list(ones)
        r2[si] = 2
        c2 = cost_of(r2, 1 if enc_L else None)
        terms.append((reps[si] - 1, c1, c2))
    if enc_L and enc_L > 1:
        c2 = cost_of(ones, 2)
        terms.append((enc_L - 1, c1, c2))

    def extra(key, sub=None):
        base = (c1[key][sub] if sub else c1[key])
        tot = base
        for mult, a, b in terms:
            av = (a[key][sub] if sub else a[key])
            bv = (b[key][sub] if sub else b[key])
            tot += mult * (bv - av)
        return tot

    out["extrapolated"] = {
        "flops": extra("flops"),
        "bytes": extra("bytes"),
        "coll_total": extra("coll", "total"),
        "coll": {k: extra("coll", k) for k in
                 ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")},
    }
    out["compile_seconds"] = time.time() - t0
    return out


def model_flops_reference(cfg: ModelConfig, shape_name: str) -> dict:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (inference), N = active params."""
    s = SHAPES[shape_name]
    n_active = M.active_param_count(cfg)
    n_total = M.param_count(cfg)
    if s.kind == "train":
        tokens = s.global_batch * s.seq_len
        mf = 6.0 * n_active * tokens
    elif s.kind == "prefill":
        tokens = s.global_batch * s.seq_len
        mf = 2.0 * n_active * tokens
    else:
        tokens = s.global_batch  # one token per request
        mf = 2.0 * n_active * tokens
    return {"model_flops": mf, "active_params": n_active,
            "total_params": n_total, "tokens": tokens}


# ------------------------------------------------------------------ driver


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             strategy_name: str = "baseline", variants: bool = True,
             out_dir: Path = ARTIFACTS) -> dict:
    cfg = get_config(arch)
    reason = skip_reason(cfg, shape_name)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "strategy": strategy_name, "n_devices": 512 if multi_pod else 256,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}__{mesh_tag}__{strategy_name}.json"
    if reason is not None:
        rec["skipped"] = reason
        path.write_text(json.dumps(rec, indent=1))
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    strategy = STRATEGIES[strategy_name]
    try:
        res = analyze_cell(cfg, shape_name, mesh, strategy=strategy,
                           variants=variants)
        rec.update(res)
        rec.update(model_flops_reference(cfg, shape_name))
        rec["ok"] = True
    except Exception as e:  # a failure here is a bug in the system
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="baseline",
                    choices=sorted(STRATEGIES))
    ap.add_argument("--no-variants", action="store_true",
                    help="skip the unrolled extrapolation compiles")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args(argv)
    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    for a in archs:
        for s in shapes:
            t0 = time.time()
            rec = run_cell(a, s, multi_pod=args.multi_pod,
                           strategy_name=args.strategy,
                           variants=not args.no_variants,
                           out_dir=Path(args.out))
            status = ("SKIP " + rec.get("skipped", "")) if "skipped" in rec \
                else ("OK" if rec.get("ok") else "FAIL " + rec.get("error", ""))
            print(f"[{time.time()-t0:7.1f}s] {a} x {s} x "
                  f"{'multi' if args.multi_pod else 'single'}: {status}",
                  flush=True)


if __name__ == "__main__":
    main()
