"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable tables).
Run: PYTHONPATH=src python -m benchmarks.run [--only fig9] [--smoke]

``--smoke`` shrinks every benchmark's knobs (sample counts, sequence
lengths, simulated latencies) so the full suite runs in CI minutes; each
script's internal invariants/assertions still execute, so perf scripts
cannot rot silently.
"""
import argparse
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run a single benchmark (e.g. fig9)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs for CI (see benchmarks.common.smoke)")
    args = ap.parse_args()
    if args.smoke:
        # set BEFORE importing benchmark modules: module-level knobs read it
        os.environ["MPIC_BENCH_SMOKE"] = "1"

    from benchmarks import (ablation_mpic_k, fig3_prefix_vs_fullreuse,
                            fig4_attention_sparsity, fig6_overlap_serving,
                            fig6_parallel_transfer, fig8_kv_distance,
                            fig9_main_comparison, fig10_sensitivity,
                            fig_cluster_throughput, fig_decode_paged,
                            fig_fault_tolerance, fig_fleet_recovery,
                            fig_prefill_paged, fig_session_resume,
                            fig_sharded_serving, roofline_table)
    suite = {
        "fig3": fig3_prefix_vs_fullreuse.main,
        "fig4": fig4_attention_sparsity.main,
        "fig6": fig6_parallel_transfer.main,
        "fig6_serving": fig6_overlap_serving.main,
        "fig8": fig8_kv_distance.main,
        "fig9": fig9_main_comparison.main,
        "fig10": fig10_sensitivity.main,
        "ablation_mpic_k": ablation_mpic_k.main,
        "decode_paged": fig_decode_paged.main,
        "prefill_paged": fig_prefill_paged.main,
        "cluster_throughput": fig_cluster_throughput.main,
        "fault_tolerance": fig_fault_tolerance.main,
        "fleet_recovery": fig_fleet_recovery.main,
        "session_resume": fig_session_resume.main,
        "sharded_serving": fig_sharded_serving.main,
        "roofline": roofline_table.main,
    }
    names = [args.only] if args.only else list(suite)
    for name in names:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        suite[name]()
        print(f"[{name} done in {time.time() - t0:.1f}s]", flush=True)


if __name__ == "__main__":
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    main()
