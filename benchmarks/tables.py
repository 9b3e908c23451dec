"""Benchmark implementations, one per paper table/figure (§VIII).

Latency numbers are the cost model's cycle estimates (the role HLS
synthesis reports play in the paper): deterministic, on any host.  Every
function returns a list of CSV rows (name, value, derived).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core import ABLATION_PRESETS, CodoOptions, PassManager, codo_opt
from repro.core.compiler import format_batch_grid  # noqa: F401 — re-export
from repro.models import dataflow_models as dm

# For rows that *report* compile time as a paper metric: a real pipeline run
# (no cache) without the diagnostics census (two whole-graph violation scans
# per pass, ~25% of a large compile) that the default manager adds.
_TIMING_MANAGER = PassManager(census=False)


@dataclass
class Row:
    name: str
    value: float
    derived: str = ""

    def csv(self) -> str:
        return f"{self.name},{self.value:.6g},{self.derived}"


# --------------------------------------------------------------------------
# Table II — kernel-level applications
# --------------------------------------------------------------------------

TABLE2 = {
    "atax": lambda: dm.atax(400, 400),
    "gesummv": lambda: dm.gesummv(400),
    "gemm": lambda: dm.gemm(256, 256, 256),
    "mvt": lambda: dm.mvt(400),
    "3mm": lambda: dm.three_mm(256),
    "residual_mlp": lambda: dm.residual_mlp(64, 512),
    "autoencoder": lambda: dm.autoencoder(64, 784),
    "residual_block": lambda: dm.residual_block(1, 64, 32),
    "dws_conv_block": lambda: dm.dws_conv_block(1, 64, 32),
    "conv3_block": lambda: dm.conv3_block(1, 3, 34),
    "feed_forward": lambda: dm.feed_forward(128, 512),
    "multi_head_attention": lambda: dm.multi_head_attention(128, 256),
}


def table2_kernels(budget: int = 900) -> list[Row]:
    rows = []
    speedups = []
    for name, build in TABLE2.items():
        g = build()
        # dse_s is a reported paper metric: real pipeline run, no census.
        c = codo_opt(g, CodoOptions(budget_units=budget), cache=None,
                     manager=_TIMING_MANAGER)
        speedups.append(c.speedup)
        rows.append(Row(
            f"table2/{name}", c.speedup,
            f"units={c.schedule_report.units_used};"
            f"fifo={c.fifo_fraction:.2f};"
            f"cycles={c.final.total_cycles:.0f};"
            f"dse_s={c.compile_seconds:.3f}"))
    geo = float(np.exp(np.mean(np.log(np.maximum(speedups, 1e-9)))))
    rows.append(Row("table2/geomean", geo, "latency speedup vs sequential"))
    return rows


# --------------------------------------------------------------------------
# Tables III & IV — DNN models
# --------------------------------------------------------------------------


def _dnn_row(tag: str, name: str, build, budget: int) -> Row:
    g = build()
    # compile_s is part of the reported row (see table2_kernels).
    c = codo_opt(g, CodoOptions(budget_units=budget), cache=None,
                 manager=_TIMING_MANAGER)
    return Row(
        f"{tag}/{name}", c.speedup,
        f"cycles={c.final.total_cycles:.3e};"
        f"compile_s={c.compile_seconds:.2f};"
        f"fifo={c.fifo_fraction:.2f};"
        f"units={c.schedule_report.units_used};"
        f"vmem_B={c.final.vmem_bytes}")


def table3_dnns(budget: int = 2048) -> list[Row]:
    models = {"resnet18": lambda: dm.resnet18(32),
              "vgg16": lambda: dm.vgg16(32),
              "mobilenet": lambda: dm.mobilenet(32)}
    return [_dnn_row("table3", n, b, budget) for n, b in models.items()]


def table4_dnns(budget: int = 2048) -> list[Row]:
    models = {"resnet18": lambda: dm.resnet18(224),
              "vgg16": lambda: dm.vgg16(224),
              "mobilenet": lambda: dm.mobilenet(224),
              "zfnet": lambda: dm.zfnet(224),
              "yolo": lambda: dm.yolo_tiny(384, 1280)}
    return [_dnn_row("table4", n, b, budget) for n, b in models.items()]


# --------------------------------------------------------------------------
# Fig. 9 / Table VI — GPT-2
# --------------------------------------------------------------------------


def gpt2_eval(budget: int = 2048) -> list[Row]:
    """Prefill (TTFT analogue) and per-token decode latency from the
    scheduled GPT-2 block graph × 24 layers."""
    rows = []
    n_layers = 24
    for s in (32, 64, 128):
        g = dm.gpt2_block(S=s, D=1024)
        c = codo_opt(g, CodoOptions(budget_units=budget))
        # blocks pipeline across layers: fill + steady-state
        block = c.final.total_cycles
        prefill_cycles = block * n_layers   # conservative: no inter-block overlap
        clock = c.options.hw.clock_hz
        ttft_ms = prefill_cycles / clock * 1e3
        rows.append(Row(f"gpt2/prefill_{s}", ttft_ms,
                        f"cycles={prefill_cycles:.3e};speedup={c.speedup:.1f}"))
    g1 = dm.gpt2_block(S=1, D=1024)
    c1 = codo_opt(g1, CodoOptions(budget_units=budget))
    per_tok_ms = c1.final.total_cycles * n_layers / c1.options.hw.clock_hz * 1e3
    rows.append(Row("gpt2/decode_tok_per_s", 1e3 / per_tok_ms,
                    f"per_tok_ms={per_tok_ms:.3f}"))
    return rows


# --------------------------------------------------------------------------
# Fig. 10 / Table VII — ablation
# --------------------------------------------------------------------------


def ablation(budget: int = 2048) -> list[Row]:
    rows = []
    workloads = {"resnet18": lambda: dm.resnet18(32),
                 "gpt2_block": lambda: dm.gpt2_block(128, 1024),
                 "yolo": lambda: dm.yolo_tiny(64, 64)}
    # Table VII's grid is data (repro.core.passes.ABLATION_PRESETS), so the
    # benchmark can never drift from the pipeline's definition of opt1..opt5.
    for wname, build in workloads.items():
        for oname in ABLATION_PRESETS:
            opt = CodoOptions.preset(oname, budget_units=budget)
            c = codo_opt(build(), opt)
            rows.append(Row(f"fig10/{wname}/{oname}", c.speedup,
                            f"fifo={c.fifo_fraction:.2f}"))
    return rows


# --------------------------------------------------------------------------
# Table VII batch grid — the compiler CLI's report + a bench suite
# --------------------------------------------------------------------------


def batch_grid_rows(results) -> list[Row]:
    """CSV rows mirroring :func:`~repro.core.compiler.format_batch_grid`
    for results/bench.
    The derived string is BatchResult.derived() — one format, shared with
    the CLI's --csv output."""
    return [Row(f"table7/{r.config}/{r.preset}",
                r.compiled.speedup if r.ok else float("nan"),
                r.derived())
            for r in results]


def table7_batch(budget: int = 2048) -> list[Row]:
    """The full model-config × opt1..opt5 grid through the batch driver."""
    from repro.core import codo_opt_batch
    from repro.core.compiler import ablation_jobs, batch_workloads

    results = codo_opt_batch(ablation_jobs(batch_workloads(),
                                           budget_units=budget))
    return batch_grid_rows(results)


# --------------------------------------------------------------------------
# Fig. 11 — resource-performance trade-off
# --------------------------------------------------------------------------


def parallelism_sweep() -> list[Row]:
    rows = []
    for budget in (64, 128, 256, 512, 1024, 2048, 4096):
        c = codo_opt(dm.resnet18(32), CodoOptions(budget_units=budget))
        rows.append(Row(f"fig11/budget_{budget}", c.speedup,
                        f"units={c.schedule_report.units_used}"))
    return rows


# --------------------------------------------------------------------------
# Table VIII — FIFO percentage
# --------------------------------------------------------------------------


def fifo_percentage() -> list[Row]:
    workloads = {"gesummv": lambda: dm.gesummv(400),
                 "residual_block": lambda: dm.residual_block(1, 64, 32),
                 "multi_head_attention": lambda: dm.multi_head_attention(128, 256),
                 "mobilenet": lambda: dm.mobilenet(32),
                 "resnet18": lambda: dm.resnet18(32),
                 "gpt2_block": lambda: dm.gpt2_block(128, 1024)}
    rows = []
    for name, build in workloads.items():
        c = codo_opt(build())
        rows.append(Row(f"table8/{name}", c.fifo_fraction * 100, "% FIFO"))
    return rows
