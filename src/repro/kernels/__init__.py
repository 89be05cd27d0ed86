# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

# Pallas kernel registry: one entry per kernel module in this package.
# docs/kernels.md documents exactly this list and tools/check_docs.py
# cross-checks the two, so adding a kernel without documenting it (or
# documenting one that does not exist) fails CI.
KERNELS: tuple[str, ...] = (
    "anytime_svm",
    "harris",
    "perforated_attention",
    "rwkv6_wkv",
    "serve_tick",
    "ssd_scan",
)
