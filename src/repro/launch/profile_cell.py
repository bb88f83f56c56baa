import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Per-cell HLO profile: where the bytes / FLOPs / collective traffic live.

A "profiler" that needs no accelerator: lowers one
(arch x shape) cell on the production mesh and prints the per-device byte
breakdown by opcode, the collective breakdown by (kind, operand size), and
the while-loop trip counts the analyzer resolved.

    PYTHONPATH=src python -m repro.launch.profile_cell \
        --arch llama4-maverick-400b-a17b --shape train_4k [--multipod]

``--sc-trace`` additionally prices the cell's dense() workload on the
SOT-MRAM array simulator (repro.arch): per-site pulse-schedule cycles and
energy at the production shape, independent of the XLA lowering.
"""

import argparse           # noqa: E402

from repro.configs import SHAPES, get_config  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.dryrun import lower_cell  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def profile(arch: str, shape: str, multi_pod: bool = False, top: int = 18):
    mesh = make_production_mesh(multi_pod=multi_pod)
    result, hc = lower_cell(arch, shape, mesh, compile_=True,
                            return_cost=True)
    rf = result["roofline"]
    print(f"\n== {arch} x {shape} x {result['mesh']} ==")
    print(f"bound={rf['bound']}  compute_s={rf['compute_s']:.3f}  "
          f"memory_s={rf['memory_s']:.3f}  "
          f"collective_s={rf['collective_s']:.3f}  "
          f"useful={rf['useful_fraction']:.3f}")
    print(f"mem/dev={result.get('memory', {}).get('total_per_device_gb')}GB  "
          f"unresolved_loops={hc.unresolved_loops}")

    total_b = sum(hc.bytes_by_opcode.values()) or 1
    print(f"\n-- bytes by opcode (per device, total "
          f"{total_b / 1e12:.2f} TB) --")
    for op, b in sorted(hc.bytes_by_opcode.items(), key=lambda kv: -kv[1])[:top]:
        print(f"  {op:<24s} {b / 1e12:9.3f} TB  {b / total_b * 100:5.1f}%")

    total_c = sum(hc.coll_by_shape.values()) or 1
    print(f"\n-- collectives by (kind, operand bytes) (per device, total "
          f"{total_c / 1e9:.2f} GB) --")
    for sk, b in sorted(hc.coll_by_shape.items(), key=lambda kv: -kv[1])[:top]:
        kind, sz = sk.rsplit(":", 1)
        print(f"  {kind:<20s} op={int(sz) / 1e6:10.1f} MB   total "
              f"{b / 1e9:9.2f} GB  {b / total_c * 100:5.1f}%")
    return result, hc


def sc_trace(arch: str, shape: str, nbit: int = 1024, top: int = 12):
    """Price the cell's dense() workload on the SOT-MRAM array simulator.

    Static analysis (repro.arch.workload): no lowering, no numerics — the
    pulse-schedule compiler runs per matmul site with explicit layer
    multiplicity, so production shapes price in milliseconds.
    """
    from repro import arch as arch_sim
    cfg = get_config(arch)
    sh = SHAPES[shape]
    tokens = sh.global_batch * (1 if sh.kind == "decode" else sh.seq_len)
    sites = arch_sim.dense_workload(cfg, tokens)
    per_site, total = arch_sim.price_workload(sites, nbit)
    print(f"\n-- SOT-MRAM array trace: {arch} x {shape} "
          f"({tokens} tokens, nbit={nbit}, spec={arch_sim.DEFAULT_SPEC}) --")
    per_site.sort(key=lambda sr: -sr[1].cycles)
    for s, r in per_site[:top]:
        print(f"  {s.label:<14s} {s.m}x{s.k}x{s.n} x{s.count:<3d} "
              f"{r.cycles:>14,d} cyc  {r.energy_pj / 1e6:10.2f} µJ  "
              f"util={r.subarray_util:.2f}")
    print(f"  {'TOTAL':<14s} {total.products:,} MULs  "
          f"{total.cycles:>14,d} cyc  {total.energy_pj / 1e6:10.2f} µJ  "
          f"util={total.subarray_util:.2f}")
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--top", type=int, default=18)
    ap.add_argument("--sc-trace", action="store_true",
                    help="also price the dense() workload on the SOT-MRAM "
                         "array simulator (repro.arch)")
    ap.add_argument("--sc-nbit", type=int, default=1024)
    args = ap.parse_args()
    profile(args.arch, args.shape, args.multipod, args.top)
    if args.sc_trace:
        sc_trace(args.arch, args.shape, args.sc_nbit, args.top)


if __name__ == "__main__":
    main()
