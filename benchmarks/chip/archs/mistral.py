from benchmarks.chip.archs.llama import *  # noqa: F401,F403
