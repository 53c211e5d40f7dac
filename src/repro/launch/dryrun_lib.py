"""Dry-run engine: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
we build ShapeDtypeStruct stand-ins (zero allocation), jit with explicit
NamedShardings derived from the logical-axis rules, ``lower().compile()``
on the production mesh, and record:

  * ``compiled.memory_analysis()``  — per-device bytes (fits 16 GB/chip?)
  * ``compiled.cost_analysis()``    — XLA's per-device FLOPs/bytes
  * ``roofline.hlo_parse``          — scan-aware FLOPs / HBM bytes /
                                      collective bytes for §Roofline

This module holds the logic; ``dryrun.py`` is the entrypoint that pins the
fake-device count BEFORE jax initialises (and is the only place that does).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.config import TrainConfig
from repro.configs import LM_ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, input_specs, shape_applicable
from repro.distributed import partitioning as pt
from repro.distributed.steps import (
    batch_axes,
    cache_axes_and_shapes,
    make_decode_step,
    make_prefill_step,
    make_train_step,
    train_state_axes,
    train_state_shapes,
)
from repro.launch.mesh import make_production_mesh
from repro.roofline.hlo_parse import parse_hlo

__all__ = ["run_cell", "run_all", "DEFAULT_OUT_DIR"]

DEFAULT_OUT_DIR = "experiments/dryrun"


def _train_tcfg(cfg) -> TrainConfig:
    # bf16 moments for the >=200B archs so state fits (DESIGN.md §6);
    # gradient accumulation halves per-microbatch activation memory.
    mdt = "bfloat16" if cfg.fsdp else "float32"
    mb = int(os.environ.get("REPRO_MICROBATCHES", "1"))  # §Perf: mb=1 minimises
    # FSDP weight-gather traffic (measured 1340 vs 2148 GB/step at mb=4)
    return TrainConfig(optimizer_dtype=mdt, microbatches=mb)


def pick_rules(cfg, shape_name: str):
    rules = dict(pt.BASE_RULES)
    # ZeRO-3 weight sharding pays a per-microbatch all-gather; it is only
    # warranted while optimizer state exists. Serve cells shard weights via
    # TP axes (expert/heads/head_dim/mlp) instead. (§Perf iteration 2)
    if SHAPES[shape_name].kind != "train":
        rules = pt.serve_rules(rules)
    if cfg.fsdp and SHAPES[shape_name].kind == "train":
        rules = pt.fsdp_rules(rules)
    if shape_name == "long_500k":
        rules = pt.long_context_rules(rules)
    return rules


def _mem_dict(ma) -> Dict[str, Any]:
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
        "peak_estimate_bytes": ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
        - ma.alias_size_in_bytes,
    }


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    reduced: bool = False,
    mesh=None,
    compile_cell: bool = True,
) -> Dict[str, Any]:
    """Lower+compile one cell; returns a JSON-serialisable record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    ok, reason = shape_applicable(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    if reduced:
        cfg = cfg.reduced()

    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    rec["devices"] = int(mesh.devices.size)
    rules = pick_rules(cfg, shape_name)
    overrides = {}
    if reduced:
        overrides = {"override_batch": min(shape.global_batch, 8),
                     "override_seq": min(shape.seq_len, 128)}
    seq = overrides.get("override_seq", shape.seq_len)
    bsz = overrides.get("override_batch", shape.global_batch)

    try:
        with pt.axis_rules(mesh, rules):
            t0 = time.time()
            if shape.kind == "train":
                tcfg = _train_tcfg(cfg)
                step = make_train_step(cfg, tcfg)
                state_sds = train_state_shapes(cfg, tcfg)
                state_sh = pt.make_shardings(train_state_axes(cfg), state_sds)
                b_sds = input_specs(cfg, shape_name, **overrides)
                b_sh = pt.make_shardings(
                    {k: v for k, v in batch_axes(cfg, "train").items() if k in b_sds},
                    b_sds,
                )
                rep = NamedSharding(mesh, PartitionSpec())
                jitted = jax.jit(
                    step,
                    in_shardings=(state_sh, b_sh),
                    out_shardings=(state_sh, rep),
                    donate_argnums=(0,),
                )
                lowered = jitted.lower(state_sds, b_sds)
            elif shape.kind == "prefill":
                step = make_prefill_step(cfg)
                from repro.layers.params import param_axes, param_shapes
                from repro.models.registry import get_model

                model = get_model(cfg)
                p_sds = param_shapes(model.schema(cfg), cfg.weight_dtype)
                p_sh = pt.make_shardings(param_axes(model.schema(cfg)), p_sds)
                c_axes, c_sds = cache_axes_and_shapes(cfg, bsz, seq)
                c_sh = pt.make_shardings(c_axes, c_sds)
                b_sds = input_specs(cfg, shape_name, **overrides)
                b_sh = pt.make_shardings(
                    {k: v for k, v in batch_axes(cfg, "prefill").items() if k in b_sds},
                    b_sds,
                )
                logits_sh = NamedSharding(mesh, pt.shape_aware_spec(
                    ("batch", "vocab"), (bsz, cfg.vocab_size)))
                jitted = jax.jit(
                    step,
                    in_shardings=(p_sh, b_sh, c_sh),
                    out_shardings=(logits_sh, c_sh),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(p_sds, b_sds, c_sds)
            else:  # decode
                step = make_decode_step(cfg)
                from repro.layers.params import param_axes, param_shapes
                from repro.models.registry import get_model

                model = get_model(cfg)
                p_sds = param_shapes(model.schema(cfg), cfg.weight_dtype)
                p_sh = pt.make_shardings(param_axes(model.schema(cfg)), p_sds)
                c_axes, c_sds = cache_axes_and_shapes(cfg, bsz, seq)
                c_sh = pt.make_shardings(c_axes, c_sds)
                tok_sds = input_specs(cfg, shape_name, **{"override_batch": bsz})
                tok_sh = pt.make_shardings(
                    {"tokens": batch_axes(cfg, "decode")["tokens"]}, tok_sds
                )
                pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
                rep = NamedSharding(mesh, PartitionSpec())
                logits_sh = NamedSharding(mesh, pt.shape_aware_spec(
                    ("batch", "vocab"), (bsz, cfg.vocab_size)))
                jitted = jax.jit(
                    step,
                    in_shardings=(p_sh, tok_sh["tokens"], c_sh, rep),
                    out_shardings=(logits_sh, c_sh),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(p_sds, tok_sds["tokens"], c_sds, pos_sds)
            rec["lower_seconds"] = round(time.time() - t0, 2)

            if not compile_cell:
                rec["status"] = "lowered"
                return rec
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_seconds"] = round(time.time() - t1, 2)

            ma = compiled.memory_analysis()
            if ma is not None:
                rec["memory"] = _mem_dict(ma)
            ca = compiled.cost_analysis()
            if ca:
                rec["cost_analysis"] = {
                    "flops": float(ca.get("flops", -1.0)),
                    "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
                }
            text = compiled.as_text()
            cost = parse_hlo(text)
            rec["parsed"] = {
                "flops": cost.flops,
                "hbm_bytes": cost.hbm_bytes,
                "collective_bytes": cost.collective_bytes,
                "collective_by_type": cost.collective_by_type,
                "collective_count": cost.collective_count,
                "while_trip_counts": cost.while_trip_counts[:20],
            }
            rec["status"] = "ok"
    except Exception as e:  # record failures as data, not crashes
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def run_all(
    archs=None,
    shapes=None,
    meshes=("single_pod", "multi_pod"),
    out_dir: str = DEFAULT_OUT_DIR,
    reduced: bool = False,
    skip_existing: bool = True,
) -> list:
    archs = archs or LM_ARCH_IDS
    shapes = shapes or list(SHAPES)
    os.makedirs(out_dir, exist_ok=True)
    results = []
    # reuse one mesh object per mesh kind (mesh creation is cheap but tidy)
    mesh_cache = {}
    for mesh_name in meshes:
        multi = mesh_name == "multi_pod"
        for arch in archs:
            for shape_name in shapes:
                path = os.path.join(out_dir, f"{mesh_name}__{arch}__{shape_name}.json")
                if skip_existing and os.path.exists(path):
                    with open(path) as f:
                        rec = json.load(f)
                    if rec.get("status") in ("ok", "skipped"):
                        results.append(rec)
                        print(f"[cached] {mesh_name} {arch} {shape_name}: {rec['status']}")
                        continue
                if mesh_name not in mesh_cache:
                    mesh_cache[mesh_name] = make_production_mesh(multi_pod=multi)
                print(f"[run]    {mesh_name} {arch} {shape_name} ...", flush=True)
                rec = run_cell(arch, shape_name, multi_pod=multi, reduced=reduced,
                               mesh=mesh_cache[mesh_name])
                results.append(rec)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f" lower={rec['lower_seconds']}s "
                             f"compile={rec['compile_seconds']}s")
                elif status == "error":
                    extra = " " + rec["error"][:120]
                print(f"[done]   {mesh_name} {arch} {shape_name}: {status}{extra}",
                      flush=True)
    return results
