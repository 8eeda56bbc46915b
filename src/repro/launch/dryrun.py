"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices, lowers the real step
function (train / prefill / decode / infer / retrieval) with the family
sharding policy, compiles, and records memory_analysis + cost_analysis +
collective bytes parsed from the post-SPMD HLO.

FLOPs/collective accounting: XLA's HloCostAnalysis visits while-loop bodies
ONCE (verified on this container), and our layers run under lax.scan.  So
each cell is compiled three times: the full config (true per-device memory)
plus L=1 and L=2 analysis variants with single-tile attention/loss/edge
chunking, from which per-layer FLOPs/bytes/collective increments are fit
linearly and extrapolated to the real depth:  X(L) = a + b*L.

Usage:
  python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out reports/dryrun]
"""
# The VERY FIRST lines, before ANY other import (jax locks device count on
# first init):
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS_EXTRA", "")
)

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs as C  # noqa: E402
from repro.distributed import policies as pol  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.gnn import gnn_loss, init_gnn  # noqa: E402
from repro.models.recsys import wide_deep  # noqa: E402
from repro.models.transformer import model as tm  # noqa: E402
from repro.training.loop import make_train_step  # noqa: E402
from repro.training.optimizer import AdamWConfig  # noqa: E402

_COLL_RE = re.compile(
    r"=\s*(\w+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b"
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes per collective kind (per-device program => local
    shapes; all-reduce counted 2x for its reduce-scatter+all-gather phases)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        n = _DTYPE_BYTES[dtype]
        for d in dims.split(","):
            if d:
                n *= int(d)
        mult = 2 if kind == "all-reduce" else 1
        out[kind] = out.get(kind, 0) + n * mult
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def _opt_cfg(spec) -> AdamWConfig:
    big = spec.family == "lm" and spec.model_cfg.param_count()[0] > 50e9
    return AdamWConfig(state_dtype="bfloat16" if big else "float32")


# ---------------------------------------------------------------------------
# per-family step builders: return (fn, example_args, in_shardings)
# ---------------------------------------------------------------------------
def build_lm(spec, shape, mesh, cfg, *, n_micro: int | None = None):
    from repro.configs.common import lm_inputs

    inputs = lm_inputs(shape, cfg)
    pspecs_fn = lambda tree: pol.lm_param_specs(
        tree, moe_mode=cfg.moe.shard_mode if cfg.moe else "expert"
    )
    dp = pol.dp_axes(mesh)
    params_shape = jax.eval_shape(lambda k: tm.init_params(k, cfg), jax.random.PRNGKey(0))

    def shard(tree, specs):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    if shape.kind == "train":
        opt_cfg = _opt_cfg(spec)

        def loss_fn(params, batch):
            return tm.lm_loss(params, batch["tokens"], batch["loss_mask"], cfg)

        # 8 microbatches: per-(device, microbatch) = 2 sequences — the knob
        # that brought train_4k from 59 GiB to <16 GiB/chip (§Perf iter 2).
        # Analysis variants pass n_micro=1 (FLOPs are microbatch-invariant,
        # and the micro-scan body would be counted once by HloCostAnalysis).
        if n_micro is None:
            n_micro = int(os.environ.get("REPRO_N_MICRO", "8"))
        init_state, step = make_train_step(loss_fn, opt_cfg, n_microbatches=n_micro)
        state_shape = jax.eval_shape(init_state, params_shape)
        psp = pspecs_fn(params_shape)
        state_specs = {
            "params": psp,
            "opt": {"m": psp, "v": psp, "step": P()},
        }
        batch_specs = {"tokens": P(dp, None), "loss_mask": P(dp, None)}
        args = (state_shape, inputs)
        shardings = (shard(None, state_specs), shard(None, batch_specs))
        return step, args, shardings, 0  # donate state

    if shape.kind == "prefill":
        cache_len = shape.params["seq_len"]
        b = pol.batch_axes_or_none(mesh, shape.params["global_batch"])

        def fn(params, tokens, true_len):
            return tm.prefill(params, tokens, true_len, cfg, cache_len)

        psp = pspecs_fn(params_shape)
        args = (params_shape, inputs["tokens"], inputs["true_len"])
        shardings = (
            shard(None, psp),
            NamedSharding(mesh, P(b, None)),
            NamedSharding(mesh, P(b)),
        )
        return fn, args, shardings, None

    # decode / long_decode
    quant = cfg.kv_quant

    def fn(params, ck, cv, cpos, cursor, token, ks=None, vs=None):
        cache = tm.KVCache(k=ck, v=cv, pos=cpos, cursor=cursor,
                           k_scale=ks, v_scale=vs)
        nxt, new_cache = tm.decode_step(params, cache, token, cfg)
        return jnp.argmax(nxt, -1).astype(jnp.int32), new_cache

    psp = pspecs_fn(params_shape)
    batch = shape.params["global_batch"]
    cs = pol.lm_cache_specs(
        mesh, batch, cfg.n_kv_heads,
        kv_shard=os.environ.get("REPRO_KV_SHARD", "seq"),
    )
    b = pol.batch_axes_or_none(mesh, batch)
    args = [
        params_shape, inputs["cache_k"], inputs["cache_v"],
        inputs["cache_pos"], inputs["cursor"], inputs["token"],
    ]
    scale_spec = NamedSharding(mesh, P(*cs["k"][:-1]))
    shardings = [
        shard(None, psp),
        NamedSharding(mesh, cs["k"]), NamedSharding(mesh, cs["v"]),
        NamedSharding(mesh, cs["pos"]), NamedSharding(mesh, cs["cursor"]),
        NamedSharding(mesh, P(b)),
    ]
    donate = (1, 2)
    if quant:
        args += [inputs["k_scale"], inputs["v_scale"]]
        shardings += [scale_spec, scale_spec]
        donate = (1, 2, 6, 7)
    return fn, tuple(args), tuple(shardings), donate


def build_gnn(spec, shape, mesh, cfg, *, edge_chunk=16384):
    from repro.configs.common import gnn_inputs

    inputs = gnn_inputs(shape, cfg)
    params_shape = jax.eval_shape(lambda k: init_gnn(k, cfg), jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig()

    def loss_fn(params, batch):
        if cfg.arch == "equiformer_v2":
            from repro.models.gnn.equiformer import apply_equiformer

            out = apply_equiformer(params, cfg, batch, edge_chunk=edge_chunk)
            tgt = batch["targets"]
            if cfg.graph_readout and "graph_ids" in batch:
                out = jax.ops.segment_sum(
                    out, batch["graph_ids"], num_segments=tgt.shape[0]
                )
            loss = jnp.mean((out - tgt) ** 2)
        else:
            loss = gnn_loss(params, cfg, batch)
        return loss, {}

    init_state, step = make_train_step(loss_fn, opt_cfg)
    state_shape = jax.eval_shape(init_state, params_shape)
    psp = pol.gnn_param_specs(params_shape)
    state_specs = {"params": psp, "opt": {"m": psp, "v": psp, "step": P()}}
    in_specs = pol.gnn_input_specs(mesh, inputs.keys())
    mk = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    return step, (state_shape, inputs), (mk(state_specs), mk(in_specs)), 0


def build_recsys(spec, shape, mesh, cfg):
    from repro.configs.common import recsys_inputs

    inputs = recsys_inputs(shape, cfg)
    mk = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    rs = pol.recsys_input_specs(mesh)
    if shape.kind == "retrieval":
        def fn(query, cand):
            from repro.kernels.topk_sim import ref as topk_ref

            return topk_ref.topk_similarity(query, cand, shape.params["k"])

        args = (inputs["query"], inputs["cand_emb"])
        shardings = (
            NamedSharding(mesh, rs["query"]), NamedSharding(mesh, rs["cand_emb"]),
        )
        return fn, args, shardings, None

    params_shape = jax.eval_shape(
        lambda k: wide_deep.init_wide_deep(k, cfg), jax.random.PRNGKey(0)
    )
    psp = pol.recsys_param_specs(params_shape)
    if shape.kind == "train":
        def loss_fn(params, batch):
            return (
                wide_deep.wide_deep_loss(
                    params, cfg, batch["dense"], batch["sparse_ids"], batch["labels"]
                ),
                {},
            )

        init_state, step = make_train_step(loss_fn, AdamWConfig())
        state_shape = jax.eval_shape(init_state, params_shape)
        state_specs = {"params": psp, "opt": {"m": psp, "v": psp, "step": P()}}
        b_specs = {k: rs[k] for k in ("dense", "sparse_ids", "labels")}
        return (
            step, (state_shape, inputs), (mk(state_specs), mk(b_specs)), 0,
        )

    def fn(params, dense, sparse_ids):
        return wide_deep.wide_deep_logits(params, cfg, dense, sparse_ids)

    args = (params_shape, inputs["dense"], inputs["sparse_ids"])
    shardings = (
        mk(psp), NamedSharding(mesh, rs["dense"]), NamedSharding(mesh, rs["sparse_ids"]),
    )
    return fn, args, shardings, None


# ---------------------------------------------------------------------------
def _analysis_cfg(spec, shape, n_layers):
    """Config variant for the linear-in-L FLOPs fit: layers UNROLLED (a
    scanned body is counted once by HloCostAnalysis) and single-tile
    attention/loss chunking so inner scan trip counts don't hide work."""
    cfg = C.effective_model_cfg(spec, shape)
    if spec.family == "lm":
        s = shape.params.get("seq_len", 4096)
        return dataclasses.replace(
            cfg, n_layers=n_layers, q_chunk=max(s, 256), kv_chunk=max(s, 256),
            loss_chunk=max(s - 1, 1), remat=False, scan_layers=False,
        )
    if spec.family == "gnn":
        return dataclasses.replace(cfg, n_layers=n_layers)
    return cfg


def _compile_cell(spec, shape, mesh, cfg, *, edge_chunk=16384, n_micro=None):
    builder = {"lm": build_lm, "gnn": build_gnn, "recsys": build_recsys}[spec.family]
    kw = {}
    if spec.family == "gnn":
        kw["edge_chunk"] = edge_chunk
    if spec.family == "lm":
        kw["n_micro"] = n_micro
    fn, args, shardings, donate = builder(spec, shape, mesh, cfg, **kw)
    jit_kw = {"in_shardings": shardings}
    if donate == 0:
        jit_kw["donate_argnums"] = (0,)
    elif isinstance(donate, tuple):
        jit_kw["donate_argnums"] = donate
    with mesh:
        lowered = jax.jit(fn, **jit_kw).lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
             skip_analysis: bool = False, edge_chunk: int = 16384) -> dict:
    spec = C.get_config(arch_id)
    shape = spec.shapes[shape_name]
    rec = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "kind": shape.kind,
    }
    if shape.kind == "skip":
        rec["status"] = "skip"
        rec["reason"] = shape.params["reason"]
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = 512 if multi_pod else 256
    t0 = time.time()

    # --- full-config compile: true memory + collective schedule -------------
    cfg_full = C.effective_model_cfg(spec, shape)
    if os.environ.get("REPRO_KV_QUANT") == "1" and spec.family == "lm":
        cfg_full = dataclasses.replace(cfg_full, kv_quant=True)
    lowered, compiled = _compile_cell(spec, shape, mesh, cfg_full,
                                      edge_chunk=edge_chunk)
    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "per_device_total": int(
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
        ),
    }
    ca = compiled.cost_analysis() or {}
    rec["cost_full_program"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }
    rec["collectives_full_program"] = collective_bytes(compiled.as_text())
    rec["compile_s_full"] = round(time.time() - t0, 1)

    # --- 2-point depth fit for scan-hidden work ------------------------------
    if not skip_analysis and spec.family in ("lm", "gnn"):
        pts = {}
        from repro.configs.common import padded_edges

        for L in (1, 2):
            cfg_l = _analysis_cfg(spec, shape, L)
            _, comp_l = _compile_cell(
                spec, shape, mesh, cfg_l,
                edge_chunk=padded_edges(shape) if spec.family == "gnn" else 16384,
                n_micro=1,
            )
            ca_l = comp_l.cost_analysis() or {}
            pts[L] = {
                "flops": float(ca_l.get("flops", 0.0)),
                "bytes": float(ca_l.get("bytes accessed", 0.0)),
                "coll": collective_bytes(comp_l.as_text())["total"],
            }
        L_full = cfg_full.n_layers
        fit = {}
        for key in ("flops", "bytes", "coll"):
            b = pts[2][key] - pts[1][key]
            a = pts[1][key] - b
            fit[key] = a + b * L_full
        rec["fit_per_device"] = {
            "flops": fit["flops"], "hbm_bytes": fit["bytes"],
            "collective_bytes": fit["coll"],
            "points": pts, "n_layers": L_full,
        }
    elif spec.family == "recsys":
        rec["fit_per_device"] = {
            "flops": rec["cost_full_program"]["flops"],
            "hbm_bytes": rec["cost_full_program"]["bytes"],
            "collective_bytes": rec["collectives_full_program"]["total"],
        }
    rec["n_devices"] = n_dev
    rec["status"] = "ok"
    rec["compile_s_total"] = round(time.time() - t0, 1)
    return rec


def all_cells():
    for arch_id in C.ARCH_IDS:
        spec = C.get_config(arch_id)
        for shape_name in spec.shapes:
            yield arch_id, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-analysis", action="store_true",
                    help="full-config compile only (no 2-point FLOPs fit)")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    for arch_id, shape_name in cells:
        for mp in meshes:
            tag = f"{arch_id}__{shape_name}__{'mp' if mp else 'sp'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[cached] {tag}")
                continue
            print(f"[run] {tag}", flush=True)
            try:
                rec = run_cell(
                    arch_id, shape_name, multi_pod=mp,
                    skip_analysis=args.skip_analysis or mp,
                )
            except Exception as e:  # record failures: they are bugs to fix
                rec = {
                    "arch": arch_id, "shape": shape_name,
                    "mesh": "2x16x16" if mp else "16x16",
                    "status": "error", "error": f"{type(e).__name__}: {e}",
                }
                print(f"  ERROR {rec['error'][:300]}", flush=True)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec.get("status") == "ok":
                mem = rec["memory"]["per_device_total"] / 2**30
                print(f"  ok mem/dev={mem:.2f} GiB "
                      f"compile={rec['compile_s_total']}s", flush=True)


if __name__ == "__main__":
    main()
