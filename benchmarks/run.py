# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import sys

sys.path.insert(0, "src")
sys.path.insert(0, ".")


def main() -> None:
    from benchmarks import bench_ablations, bench_case_study, bench_paper_figures

    rows = []
    rows += bench_paper_figures.run_all()
    rows += bench_case_study.run_all()
    rows += bench_ablations.run_all()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
