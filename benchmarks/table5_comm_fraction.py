"""Paper Table 5 (+ supplement Sec. 11): all-to-all share of synchronous-EP
inference time, vs model (XL/G), device count (4/8) and batch size
(4/8/16/32).

The paper measures 50-80% on PCIe GPUs.  Here the share is modeled from
the roofline terms on the paper's hardware constants (PCIe 4090s:
~165 TF bf16, PCIe ~25 GB/s effective) and on the TPU v5e target, showing
how interconnect bandwidth moves the bottleneck — the quantity that
motivates DICE.
"""
from __future__ import annotations

from dataclasses import dataclass

from benchmarks import common
from repro.common.config import HW
from repro.configs.dit_moe_g import config as g_config
from repro.configs.dit_moe_xl import config as xl_config


@dataclass
class HwPoint:
    name: str
    flops: float
    link_bw: float     # bytes/s effective per device


from repro.metrics.latency_model import PAPER_HW, TPU_HW, layer_compute_flops

HWS = [
    HwPoint("rtx4090_pcie", PAPER_HW["flops"], PAPER_HW["link_bw"]),
    HwPoint("tpu_v5e_ici", TPU_HW["flops"], TPU_HW["link_bw"]),
]


def comm_fraction(cfg, *, local_batch, n_dev, hw: HwPoint) -> float:
    tokens = local_batch * cfg.patch_tokens
    d = cfg.d_model
    # per-layer compute from the serving latency model's single source of
    # truth (QKV+O projections are 8*T*d^2 and QK^T+AV are 4*T^2*d)
    t_comp = layer_compute_flops(cfg, tokens) / hw.flops
    cap = tokens * cfg.experts_per_token * cfg.capacity_factor
    a2a = 2 * cap * d * 2 * (n_dev - 1) / n_dev
    t_comm = a2a / hw.link_bw
    return t_comm / (t_comm + t_comp)


def run():
    for cfg_fn, mname in ((xl_config, "xl"), (g_config, "g")):
        cfg = cfg_fn()
        for hw in HWS:
            for n_dev in (4, 8):
                for b in (4, 8, 16, 32):
                    f = comm_fraction(cfg, local_batch=b, n_dev=n_dev, hw=hw)
                    common.csv_row(
                        f"table5/{mname}/{hw.name}/dev{n_dev}/b{b}", 0.0,
                        f"alltoall_time_fraction={f:.3f}")


def main():
    common.enable_compile_cache()
    run()


if __name__ == "__main__":
    main()
