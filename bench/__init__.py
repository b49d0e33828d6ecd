"""DuetServe serving benchmark: see bench/run.py and PERF.md."""
