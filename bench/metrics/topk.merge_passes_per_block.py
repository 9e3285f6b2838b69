"""Merge passes per grid step of the top-k kernel: the summed
``EngineStats.topk_merge_passes`` of the served chunks over their
``EngineStats.topk_blocks`` (grid steps that computed distances), from
the program's counters (top-k kernels, ``kernels/fused_topk.py``). A
pass inserts one candidate into a running top-k, so this is the merge's
work per block: k for a merge that always runs k passes."""


def read(rec):
    counters = rec.window.stats.get("trace", {}).get("counters", {})
    if not counters.get("engine.topk_blocks") or \
            "engine.topk_merge_passes" not in counters:
        return None
    return counters["engine.topk_merge_passes"] / \
        counters["engine.topk_blocks"]
