"""Roofline analysis — reads the dry-run JSONs and derives the three terms
per (arch × shape) cell on the single-pod mesh (EXPERIMENTS.md §Roofline).

  compute    = HLO_FLOPs/device        / peak bf16 FLOP/s
  memory     = HLO_bytes/device        / peak HBM bytes/s
  collective = collective_bytes/device / per-link ICI bytes/s (conservative
               single-link figure; result-shape bytes of every collective in
               the partitioned HLO, async pairs deduped)

The peaks come from :data:`PEAKS`, keyed by the chip's ``device_kind``:
the chip the run is on where that is a TPU, else the chip the dry-run
meshes describe (:data:`TARGET_KIND`).  A TPU missing from the table is an
error, never a default.

HLO FLOP/byte totals come from the unrolled accounting extrapolation
(``accounting.extrapolated``) because XLA's HloCostAnalysis counts scan
bodies once (see launch/dryrun.py).  MODEL_FLOPS = 6·N·D (train) or 2·N·D
(prefill/decode), N = non-embedding (dense) / active (MoE) params — the
MODEL/HLO ratio exposes remat recompute, causal-masking waste, capacity
overprovisioning and padding.

The registered case also models the CQR2 kernel pipeline's HBM terms —
fused (2 tall sweeps for R, 3 + Q₁ write for full Q) vs unfused (4 sweeps,
2 tall writes) at reference TSQR shapes: pure bytes/bandwidth arithmetic,
so it runs everywhere and the fused/unfused ratio is hard-gated.  The
dry-run half skips cleanly when no artifacts exist (the CI smoke tier);
when they do exist it reports cell counts and per-cell roofline fractions
(warn-gated — artifact sets evolve).
"""
from __future__ import annotations

import glob
import json
import os

import numpy as np

from repro.bench.registry import bench_case
from repro.bench.schema import Metric

# Published per-chip peaks, keyed by jax's ``device_kind``.  TPU v5e:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s ICI; the per-link figure is a conservative 50 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# The chip the dry-run meshes (16x16 single pod) describe.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str | None = None) -> dict:
    """Peaks of ``device_kind`` — by default the TPU this process runs on,
    or :data:`TARGET_KIND` when it runs on no TPU.  Raises for a kind that
    is not in :data:`PEAKS`."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        device_kind = dev.device_kind if dev.platform == "tpu" else TARGET_KIND
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add its "
            "FLOP/s, HBM and ICI figures with their source to PEAKS"
        ) from None


__all__ = ["PEAKS", "TARGET_KIND", "advice", "analyze_record", "case",
           "cqr2_rows", "load_all", "main", "markdown_table", "peaks",
           "tuned_markdown", "tuned_tables"]

# Reference tall-skinny shapes for the CQR2 HBM model (per-rank panels of
# the production TSQR: m_local × n at bf16).
CQR2_SHAPES = ((1 << 20, 128), (1 << 22, 256), (1 << 24, 512))


def cqr2_rows(shapes=CQR2_SHAPES, dtype: str = "bfloat16",
              hbm_bw: float | None = None) -> list[dict]:
    """HBM-traffic model of CholeskyQR2, fused vs unfused pipelines.

    The coefficients are *measured*, not restated: each pipeline runs at two
    small probe heights under :func:`repro.kernels.traffic.track_traffic`
    (the same traffic notes the hard-gated ``kernels`` case gates), and the
    exact affine-in-m byte totals are extrapolated to the target shape.  A
    pipeline change (say, a variant growing a third sweep) therefore shows
    up here automatically rather than leaving stale constants behind.
    Expected shape of the result: unfused ≈ 4 panel reads + 2 panel writes,
    fused full-Q ≈ 3 + 2, fused R-only = exactly 2 reads and no tall write.
    """
    import jax.numpy as jnp

    from repro.kernels import ops, traffic

    hbm_bw = hbm_bw or peaks()["hbm_bw"]
    dt = jnp.dtype(dtype)
    pipelines = {
        "unfused": lambda a: ops.cholesky_qr2(a, fused=False),
        "fused_q": lambda a: ops.cholesky_qr2(a),
        "fused_r": lambda a: ops.cholesky_qr2_r(a),
    }

    def measured(m, n, run):
        with traffic.track_traffic() as t:
            run(jnp.zeros((m, n), dt))      # traffic depends on shapes only
        return t.read_bytes + t.write_bytes

    rows = []
    for m, n in shapes:
        m1, m2 = 2 * n, 4 * n               # cheap probes; totals affine in m
        by = {}
        for name, run in pipelines.items():
            b1, b2 = measured(m1, n, run), measured(m2, n, run)
            by[name] = b1 + (b2 - b1) * (m - m1) // (m2 - m1)
        rows.append({
            "m": m, "n": n,
            "unfused_bytes": by["unfused"],
            "fused_q_bytes": by["fused_q"],
            "fused_r_bytes": by["fused_r"],
            "unfused_s": by["unfused"] / hbm_bw,
            "fused_q_s": by["fused_q"] / hbm_bw,
            "fused_r_s": by["fused_r"] / hbm_bw,
            "speedup_r": by["unfused"] / by["fused_r"],
            "speedup_q": by["unfused"] / by["fused_q"],
        })
    return rows


def active_params(cfg) -> tuple[int, int]:
    """(total_non_embedding, active_non_embedding) parameter counts."""
    import jax

    from repro.models import api

    specs = api.param_specs(cfg)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    total = active = 0
    for path, leaf in flat:
        name = str(path[-1])
        size = int(np.prod(leaf.shape))
        if "embed" in str(path):
            continue
        total += size
        if "we_" in name:                # routed experts
            active += int(size * cfg.top_k / max(cfg.n_experts, 1))
        else:
            active += size
    return total, active


def model_flops(cfg, kind: str, global_batch: int, seq: int) -> float:
    _, n_active = active_params(cfg)
    if kind == "train":
        return 6.0 * n_active * global_batch * seq
    if kind == "prefill":
        return 2.0 * n_active * global_batch * seq
    return 2.0 * n_active * global_batch        # decode: 1 token/row


def structural_memory_bytes(cfg, rec) -> float:
    """Per-device HBM traffic model for one step.

    XLA's ``bytes accessed`` counts logical operand bytes per op with no
    fusion awareness (~100× HBM on CPU-lowered modules), so the memory
    term uses a structural model instead:

      train:   3× params (fwd read, bwd read, update write) + 4× Adam
               moments (m,v read+write, f32) + 2× activation carries
               (save + consume), all per device;
      prefill: 1× params + activations + KV-cache write;
      decode:  1× params + full cache read + state/cache write.

    The HLO figure is still recorded as ``hlo_bytes_dev`` for reference.
    """
    import numpy as np

    from repro.launch.shardings import param_bytes as pb

    n_model = 16
    n_data = rec["n_devices"] // n_model
    kind = rec["kind"]
    params_total = pb(cfg)
    b_loc = max(rec["global_batch"] // n_data, 1)
    s = rec["seq_len"]
    act_carry = (
        cfg.n_layers * b_loc * s * cfg.d_model * 2
        / (n_model if rec.get("seq_parallel") else 1)
        / max(rec.get("microbatches", 1), 1)
    )
    if kind == "train":
        # FSDP still reads the whole model per device per step (gathered
        # slices stream through); moments stay sharded
        params_traffic = 3 * (params_total / n_model)
        opt_traffic = 4 * params_total * 4 / rec["n_devices"]
        return params_traffic + opt_traffic + 2 * act_carry * rec.get("microbatches", 1)
    if kind == "prefill":
        kv = 2 * cfg.n_layers * b_loc * min(s, 10**9) * cfg.n_kv_heads * cfg.d_head * 2
        kv /= n_model
        return params_total / n_model + act_carry + kv
    # decode: one token per row
    cache_bytes = 0.0
    try:
        from repro.models import api

        specs = api.decode_cache_specs(cfg, rec["global_batch"], s)
        cache_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in __import__("jax").tree.leaves(specs)
        ) / rec["n_devices"]
    except Exception:
        pass
    return params_total / n_model + 2 * cache_bytes


def analyze_record(rec: dict) -> dict | None:
    from repro.configs.base import get_config

    if rec.get("kind") == "tsqr" or rec.get("mesh") != "16x16":
        return None
    cfg = get_config(rec["arch"])
    n_dev = rec["n_devices"]
    ext = rec.get("accounting", {}).get("extrapolated", {})
    flops_dev = ext.get("cost.flops", rec["cost"].get("flops", 0.0))
    bytes_dev = structural_memory_bytes(cfg, rec)
    hlo_bytes_dev = ext.get(
        "cost.bytes accessed", rec["cost"].get("bytes accessed", 0.0)
    )
    coll_dev = ext.get("coll.total_bytes", rec["collectives"]["total_bytes"])
    peak = peaks()
    t_compute = flops_dev / peak["flops"]
    t_memory = bytes_dev / peak["hbm_bw"]
    t_coll = coll_dev / peak["ici_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, rec["kind"], rec["global_batch"], rec["seq_len"])
    ratio = mf / (flops_dev * n_dev) if flops_dev else 0.0
    bound = max(terms.values())
    frac = (mf / n_dev / peak["flops"]) / bound if bound else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "kind": rec["kind"],
        "compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": flops_dev * n_dev,
        "hlo_bytes_dev": hlo_bytes_dev,
        "useful_ratio": ratio,
        "roofline_frac": frac,
        "hbm_gb": rec["memory"].get("total_hbm_bytes", 0) / 1e9,
        "microbatches": rec.get("microbatches", 1),
        "seq_parallel": rec.get("seq_parallel", False),
        "gather_axis": rec.get("gather_axis"),
    }


def load_all(dirpath: str = "results/dryrun") -> list[dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*_single.json"))):
        with open(path) as f:
            rec = json.load(f)
        row = analyze_record(rec)
        if row:
            out.append(row)
    return out


def advice(row: dict) -> str:
    d = row["dominant"]
    if d == "compute" and row["useful_ratio"] < 0.5:
        return "compute-bound but <50% useful: cut remat recompute / causal-dense waste"
    if d == "compute":
        return "compute-bound: good; push MXU utilization via layout/fusion"
    if d == "memory":
        return "HBM-bound: fuse elementwise chains, widen arithmetic intensity"
    return "collective-bound: reshard (EP/SP), overlap collectives with compute"


def markdown_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant | "
           "MODEL/HLO | roofline frac | HBM GB/dev | notes |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    body = ""
    for r in rows:
        body += (f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
                 f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
                 f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
                 f"{r['roofline_frac']:.2f} | {r['hbm_gb']:.1f} | "
                 f"{advice(r)} |\n")
    return hdr + body


def tuned_tables(dirpath: str | None = None) -> list[dict]:
    """Every valid persisted autotune table under ``results/autotune/``
    (skipping stale-schema files — they must be re-tuned, not re-read)."""
    from repro.kernels import autotune as at

    dirpath = dirpath or at.DEFAULT_OUT_DIR
    docs = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        try:
            docs.append(at.load_table(path))
        except (at.AutotuneError, json.JSONDecodeError, OSError):
            continue
    return docs


def tuned_markdown(docs: list[dict]) -> str:
    """The tuned-model report section: measured machine constants and the
    per-entry roofline predictions next to the timed winners, plus the
    CQR2 HBM model re-priced at the *measured* bandwidth."""
    out = "\n## Tuned kernel model (results/autotune/, DESIGN.md §13)\n\n"
    for doc in docs:
        mc = doc["machine"]
        out += (f"backend **{doc['backend']}** (arch `{doc['arch']}`): "
                f"measured bw {mc['mem_bw_bytes_per_s']:.3e} B/s, "
                f"peak {mc['flops_per_s']:.3e} flop/s\n\n")
        out += ("| kernel | shape class | block_rows | floor | fuse | "
                "predicted s | measured s |\n"
                "|---|---|---|---|---|---|---|\n")
        for _, e in sorted(doc["entries"].items()):
            out += (f"| {e['kernel']} | {e['shape_class']} | "
                    f"{e['block_rows']} | {e['gemm_width_floor']} | "
                    f"{e['fuse_want_q']} | {e['predicted_s']:.3e} | "
                    f"{e['measured_s']:.3e} |\n")
        out += (
            "\nCQR2 HBM model at the measured bandwidth "
            "(fused R-only vs unfused):\n\n"
            "| shape | unfused s | fused-R s | speedup |\n|---|---|---|---|\n"
        )
        for r in cqr2_rows(hbm_bw=mc["mem_bw_bytes_per_s"]):
            out += (f"| {r['m']}x{r['n']} | {r['unfused_s']:.3e} | "
                    f"{r['fused_r_s']:.3e} | {r['speedup_r']:.2f} |\n")
        out += "\n"
    return out


def case(dirpath: str = "results/dryrun"):
    # -- CQR2 kernel-pipeline HBM model: runs everywhere, ratio hard-gated --
    metrics = {}
    for r in cqr2_rows():
        key = f"m{r['m']}_n{r['n']}"
        metrics[f"cqr2_speedup_r_{key}"] = Metric(
            r["speedup_r"], gate="hard", direction="higher"
        )
        metrics[f"cqr2_fused_r_hbm_s_{key}"] = Metric(
            r["fused_r_s"], gate="warn", direction="lower", unit="s"
        )
        metrics[f"cqr2_unfused_hbm_s_{key}"] = Metric(
            r["unfused_s"], gate="warn", direction="lower", unit="s"
        )
    # -- dry-run roofline cells: need the artifacts ------------------------
    rows = load_all(dirpath)
    if not rows:
        metrics["n_cells"] = Metric(0, gate="warn", direction="higher")
        return metrics
    metrics["n_cells"] = Metric(len(rows), gate="warn", direction="higher")
    for r in rows:
        key = f"{r['arch']}_{r['shape']}_{r['kind']}"
        metrics[f"roofline_frac_{key}"] = Metric(
            r["roofline_frac"], gate="warn", direction="higher"
        )
        metrics[f"useful_ratio_{key}"] = Metric(
            r["useful_ratio"], gate="warn", direction="higher"
        )
    return metrics


bench_case("roofline", tags=("roofline", "dryrun"))(case)


def main():
    print("# CQR2 HBM roofline (bf16 panels): fused vs unfused pipeline")
    print("m,n,unfused_s,fused_q_s,fused_r_s,speedup_q,speedup_r")
    for r in cqr2_rows():
        print(f"{r['m']},{r['n']},{r['unfused_s']:.4e},{r['fused_q_s']:.4e},"
              f"{r['fused_r_s']:.4e},{r['speedup_q']:.2f},{r['speedup_r']:.2f}")
    rows = load_all()
    print("# roofline terms per (arch x shape), single-pod 16x16")
    print("arch,shape,kind,compute_s,memory_s,collective_s,dominant,"
          "useful_ratio,roofline_frac,hbm_gb_dev")
    for r in rows:
        print(f"{r['arch']},{r['shape']},{r['kind']},{r['compute_s']:.4e},"
              f"{r['memory_s']:.4e},{r['collective_s']:.4e},{r['dominant']},"
              f"{r['useful_ratio']:.3f},{r['roofline_frac']:.3f},{r['hbm_gb']:.1f}")
    os.makedirs("results", exist_ok=True)
    docs = tuned_tables()
    with open("results/roofline.md", "w") as f:
        f.write(markdown_table(rows))
        if docs:
            f.write(tuned_markdown(docs))
    return rows


if __name__ == "__main__":
    main()
