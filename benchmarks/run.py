# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import sys


def main() -> None:
    from benchmarks import (
        ablations,
        kernels_bench,
        matrix_bench,
        paper_figs,
        pod_tuning,
        serving_bench,
    )
    from benchmarks.common import begin

    benches = [
        paper_figs.bench_fig1_tradeoff,
        paper_figs.bench_fig3_4_single_constraint,
        paper_figs.bench_fig5_6_dual_constraint,
        paper_figs.bench_fig7_10_generalization,
        paper_figs.bench_table4_space_sizes,
        paper_figs.bench_iteration_budget,
        kernels_bench.bench_dcov_kernel,
        kernels_bench.bench_flash_attention_kernel,
        kernels_bench.bench_ssd_kernel,
        kernels_bench.bench_coral_iteration_overhead,
        kernels_bench.bench_analytics_suite,
        pod_tuning.bench_pod_tuning_from_artifacts,
        serving_bench.bench_serving_suite,
        matrix_bench.bench_matrix_suite,
        ablations.bench_ablation_step_floor,
        ablations.bench_ablation_probe_policy,
    ]
    begin()
    failures = 0
    for b in benches:
        try:
            b()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{b.__name__},0.0,ERROR:{type(e).__name__}:{e}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
