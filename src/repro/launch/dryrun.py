import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against ShapeDtypeStruct inputs (no allocation), record
memory_analysis / cost_analysis / loop-aware roofline terms to JSON.

The two lines above MUST precede any other import (jax locks the device
count at first init).

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k \
      --mesh pod --out experiments/dryrun
  python -m repro.launch.dryrun --all            # every remaining cell
  python -m repro.launch.dryrun --report         # summarize JSONs
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, SHAPES, input_specs       # noqa: E402
from repro.launch import roofline                          # noqa: E402
from repro.launch.mesh import make_production_mesh, shard_ctx  # noqa: E402
from repro.models import init_params_shape, param_count, shardings  # noqa: E402
from repro.models.model import REMAT_POLICIES  # noqa: F401,E402
from repro.optim import AdamWConfig, adamw      # noqa: E402
from repro.serving import serve_state_specs     # noqa: E402
from repro.serving.serve_step import decode_step, prefill, \
    serve_state_shardings                       # noqa: E402
from repro.train import make_train_step         # noqa: E402

# per-arch execution knobs (microbatches divide the 256 train batch;
# int8 Adam moments for the >=50B archs so optimizer state fits HBM)
TRAIN_KNOBS = {
    "xlstm-125m": dict(n_micro=1, moments="float32"),
    "musicgen-medium": dict(n_micro=2, moments="float32"),
    "deepseek-7b": dict(n_micro=4, moments="float32"),
    "codeqwen1.5-7b": dict(n_micro=4, moments="float32"),
    "llava-next-mistral-7b": dict(n_micro=4, moments="float32"),
    "gemma2-27b": dict(n_micro=8, moments="float32"),
    "qwen1.5-110b": dict(n_micro=16, moments="int8"),
    "mixtral-8x22b": dict(n_micro=16, moments="int8"),
    "deepseek-v2-236b": dict(n_micro=16, moments="int8"),
    "jamba-1.5-large-398b": dict(n_micro=16, moments="int8"),
}


def batch_shardings(spec_tree, sctx):
    """Batch inputs: dim0 over (pod,)data when divisible."""
    def one(s):
        b = sctx.batch_axes if s.shape[0] % sctx._bsz() == 0 else None
        return NamedSharding(sctx.mesh, P(b, *([None] * (len(s.shape) - 1))))
    return jax.tree.map(one, spec_tree)


def _opt_shardings(opt_shape, param_sh, mesh):
    """Adam moments follow their param's sharding.  int8 block-quantized
    moments are flat [nblocks, 64] — block order is param-agnostic, so they
    shard over every non-pod mesh axis (fully sharded optimizer state)."""
    flat_axes = tuple(a for a in mesh.axis_names if a != "pod")

    def visit(path, leaf):
        keys = [getattr(p, "key", None) for p in path]
        if keys[0] in ("m", "v"):
            if keys[-1] in ("q", "scale"):
                n_flat = 1
                for a in flat_axes:
                    n_flat *= mesh.shape[a]
                if leaf.shape[0] % n_flat == 0:
                    rest = [None] * (len(leaf.shape) - 1)
                    return NamedSharding(mesh, P(flat_axes, *rest))
                return NamedSharding(mesh,
                                     P(*([None] * len(leaf.shape))))
            sub = param_sh
            for k in keys[1:]:
                if isinstance(sub, dict) and k in sub:
                    sub = sub[k]
                else:
                    sub = None
                    break
            if sub is not None and not isinstance(sub, dict) \
                    and len(leaf.shape) == len(sub.spec):
                return sub
        return NamedSharding(mesh, P(*([None] * len(leaf.shape))))
    return jax.tree_util.tree_map_with_path(visit, opt_shape)


def lower_cell(arch: str, shape: str, mesh_kind: str, remat: str = "full",
               extra: dict | None = None):
    """Returns (lowered, n_chips, meta) for one dry-run cell."""
    cfg = ARCHS[arch]
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    sctx = shard_ctx(mesh)
    n_chips = mesh.size
    params_shape = init_params_shape(cfg)
    param_sh = shardings(params_shape, cfg, sctx)
    inputs = input_specs(cfg, shape)
    knobs = dict(TRAIN_KNOBS[arch])
    knobs.update(extra or {})

    meta = dict(arch=arch, shape=shape, mesh=mesh_kind, n_chips=n_chips,
                remat=remat, **{k: str(v) for k, v in knobs.items()})

    if cell.kind == "train":
        # each microbatch must still split over every batch shard, or the
        # partitioner replicates activations across the starved axis
        batch_shards = sctx._bsz()
        knobs["n_micro"] = min(int(knobs["n_micro"]),
                               max(1, cell.global_batch // batch_shards))
        opt_cfg = AdamWConfig(moment_dtype=knobs["moments"],
                              total_steps=10000)
        opt_shape = jax.eval_shape(lambda p: adamw.init(p, opt_cfg),
                                   params_shape)
        opt_sh = _opt_shardings(opt_shape, param_sh, mesh)
        step = make_train_step(cfg, opt_cfg, sctx=sctx,
                               n_microbatches=int(knobs["n_micro"]),
                               remat=remat)
        batch_sh = batch_shardings(inputs, sctx)
        jitted = jax.jit(step, in_shardings=(param_sh, opt_sh, batch_sh),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_shape, opt_shape, inputs)
        tokens = cell.global_batch * cell.seq_len
        meta["model_flops"] = 6 * param_count(cfg, active_only=True) * tokens
    elif cell.kind == "prefill":
        def prefill_fn(params, batch):
            return prefill(params, cfg,
                           tokens=batch.get("tokens"),
                           embeds=batch.get("embeds"),
                           max_len=cell.seq_len, budget=0, sctx=sctx,
                           remat=remat)
        batch_sh = batch_shardings(inputs, sctx)
        jitted = jax.jit(prefill_fn, in_shardings=(param_sh, batch_sh))
        lowered = jitted.lower(params_shape, inputs)
        tokens = cell.global_batch * cell.seq_len
        meta["model_flops"] = 2 * param_count(cfg, active_only=True) * tokens
    else:  # decode
        import dataclasses as _dc
        if knobs.get("serve_sharding", "resident") == "resident":
            # inference-mode placement: weights resident (no FSDP gather
            # per token); see sharding._serve_rule + EXPERIMENTS.md §Perf
            sctx = _dc.replace(sctx, mode="serve")
            param_sh = shardings(params_shape, cfg, sctx)
            meta["serve_sharding"] = "resident"
        B = cell.global_batch
        state_shape = serve_state_specs(cfg, B, cell.seq_len,
                                        budget=cell.bounded_budget)
        state_sh = serve_state_shardings(cfg, sctx, state_shape)

        def decode_fn(params, state, inp):
            return decode_step(params, cfg, state,
                               token=inp.get("token"),
                               embed=inp.get("embed"), sctx=sctx)
        in_sh = batch_shardings(inputs, sctx)
        jitted = jax.jit(decode_fn, in_shardings=(param_sh, state_sh, in_sh),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_shape, state_shape, inputs)
        meta["model_flops"] = 2 * param_count(cfg, active_only=True) * B
        meta["bounded_budget"] = cell.bounded_budget
    return lowered, n_chips, meta


def kernel_credit_bytes(cfg, cell, n_chips: int, passes: float) -> float:
    """Per-chip HBM bytes of the Pallas flash/flash-decode kernels for every
    attention layer of one step — the analytic substitute for the
    jnp-lowered attention-inner traffic (which materializes score tensors
    that the kernels keep in VMEM).  Model:
      full-seq:  passes x [ nq x (K+V) streamed + Q + O ]
      decode:    2K + V + Q + O  (stats pass re-reads K)
    Head/batch sharding divides per-chip bytes; windowed layers stream a
    band instead of the full prefix.
    """
    tp_n = 16
    bsz = 16 * (2 if n_chips == 512 else 1)
    B, S = cell.global_batch, cell.seq_len
    if cell.kind == "decode" and cell.bounded_budget:
        S = cell.bounded_budget          # the DAC pool bounds the KV read
    B_loc = B / bsz if B % bsz == 0 else B
    H = cfg.n_heads
    Hkv = cfg.n_kv_heads
    hd = cfg.head_dim
    H_loc = H / tp_n if H % tp_n == 0 else H
    Hkv_loc = Hkv / tp_n if Hkv % tp_n == 0 else Hkv
    bq = min(cfg.attn_chunk_q, S)
    total = 0.0
    # slot tables shard over 'model' when kv-heads don't divide it
    # (serve_state_shardings); the kernel streams only the local slots
    slot_div = tp_n if Hkv % tp_n else 1
    for spec in cfg.layer_specs():
        if spec.kind == "mla":
            width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
            if cell.kind == "decode":
                total += B_loc * S * width * 2 * 2 / tp_n  # latent, sharded
                total += 2 * B_loc * H_loc * hd * 2
            else:
                nq = max(S // bq, 1)
                total += passes * (nq * B_loc * S * width * 2
                                   + 2 * B_loc * S * H_loc *
                                   (cfg.qk_nope_head_dim
                                    + cfg.qk_rope_head_dim) * 2)
        elif spec.kind == "attn":
            span = min(S, (spec.window or S) + bq)
            if cell.kind == "decode":
                kv = B_loc * min(S, spec.window or S) * Hkv_loc * hd * 2 \
                    / slot_div
                total += 3 * kv + 2 * B_loc * H_loc * hd * 2
            else:
                nq = max(S // bq, 1)
                kv_stream = nq * 2 * B_loc * span * Hkv_loc * hd * 2
                qo = 2 * B_loc * S * H_loc * hd * 2
                total += passes * (kv_stream + qo)
    return total


def run_cell(arch: str, shape: str, mesh_kind: str, out_dir: str,
             remat: str = "full", tag: str = "", extra: dict | None = None):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{arch}__{shape}__{mesh_kind}" + (f"__{tag}" if tag else "")
    path = os.path.join(out_dir, name + ".json")
    t0 = time.perf_counter()
    try:
        lowered, n_chips, meta = lower_cell(arch, shape, mesh_kind,
                                            remat=remat, extra=extra)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        ana = roofline.analyze_hlo(hlo, default_group=n_chips)
        terms = ana["terms"]
        model_flops_chip = meta["model_flops"] / n_chips
        result = {
            **meta,
            "ok": True,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "total_nonaliased_gb": round(
                    (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes
                     - mem.alias_size_in_bytes) / 2**30, 3),
            },
            "xla_cost": {k: cost[k] for k in ("flops",)
                         if k in cost},
            "roofline": {
                "flops_per_chip": ana["flops"],
                "hbm_bytes_per_chip": ana["hbm_bytes"],
                "wire_bytes_per_chip": ana["wire_bytes"],
                "collective_bytes": ana["collective_bytes"],
                "collective_counts": ana["collective_counts"],
                "hbm_by_op": ana.get("hbm_by_op", {}),
                "compute_s": terms["compute_s"],
                "memory_s": terms["memory_s"],
                "collective_s": terms["collective_s"],
                "dominant": roofline.dominant_term(terms),
                "model_flops_per_chip": model_flops_chip,
                "useful_flops_ratio": (model_flops_chip / ana["flops"])
                if ana["flops"] else 0.0,
                "roofline_fraction": (model_flops_chip / roofline.PEAK_FLOPS)
                / max(max(terms.values()), 1e-30),
            },
        }
        # Pallas-kernel credit: the flash kernels keep attention
        # intermediates in VMEM; the jnp-lowered graph (what CPU XLA can
        # compile) spills them.  Report the kernel-credited memory term
        # alongside the raw one (EXPERIMENTS.md §Roofline method).
        cell = SHAPES[shape]
        cfg = ARCHS[arch]
        passes = {"train": 4.0, "prefill": 1.0, "decode": 1.0}[cell.kind]
        attn_inner = ana.get("hbm_attention_inner", 0.0)
        k_bytes = kernel_credit_bytes(cfg, cell, n_chips, passes)
        mem_credited = (ana["hbm_bytes"] - attn_inner + k_bytes) \
            / roofline.HBM_BW
        terms_k = dict(terms, memory_s=mem_credited)
        result["roofline"]["kernel_credited"] = {
            "attention_inner_bytes": attn_inner,
            "kernel_bytes": k_bytes,
            "memory_s": mem_credited,
            "dominant": roofline.dominant_term(terms_k),
            "roofline_fraction":
                (model_flops_chip / roofline.PEAK_FLOPS)
                / max(max(terms_k.values()), 1e-30),
        }
    except Exception as e:  # noqa: BLE001 — cell failures are data
        result = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                  "ok": False, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    from repro.bench.results import atomic_write_json
    atomic_write_json(path, result)
    dom = result.get("roofline", {}).get("dominant", "-")
    rf = result.get("roofline", {}).get("roofline_fraction", 0)
    print(f"[dryrun] {name}: ok={result['ok']} dominant={dom} "
          f"roofline_frac={rf:.3f} ({time.perf_counter()-t0:.0f}s)")
    return result


def all_cells():
    for arch in ARCHS:
        for shape in SHAPES:
            for mesh_kind in ("pod", "multipod"):
                yield arch, shape, mesh_kind


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--tag", default="")
    ap.add_argument("--n-micro", type=int, default=0)
    ap.add_argument("--serve-sharding", default="resident",
                    choices=["resident", "fsdp"],
                    help="decode param placement (fsdp = the pre-perf-"
                         "iteration baseline)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    extra = {"n_micro": args.n_micro} if args.n_micro else {}
    extra["serve_sharding"] = args.serve_sharding
    extra = extra or None
    if args.all:
        for arch, shape, mesh_kind in all_cells():
            p = os.path.join(args.out, f"{arch}__{shape}__{mesh_kind}.json")
            if args.skip_done and os.path.exists(p):
                with open(p) as f:
                    if json.load(f).get("ok"):
                        continue
            run_cell(arch, shape, mesh_kind, args.out, remat=args.remat)
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        run_cell(args.arch, args.shape, args.mesh, args.out,
                 remat=args.remat, tag=args.tag, extra=extra)


if __name__ == "__main__":
    main()
