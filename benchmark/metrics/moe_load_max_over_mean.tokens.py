"""Layer 'layers': the routed experts' load, busiest held expert over the
mean.  From the program's counters (``Trainer`` folds the step's device
scalars in on the steps whose loss the listener read):
``tpudl_moe_pairs_max_expert_total`` times the experts held over
``tpudl_moe_pairs_total``, over the window.  1 is an even load; a program
without the counters, or a window in which no loss was read, gives
``None``."""


def read(obs):
    if obs["mix"]["unit"] != "tokens":
        return None
    before, after = obs["counters"]["before"], obs["counters"]["after"]
    grown = {}
    for name in ("tpudl_moe_pairs_total", "tpudl_moe_pairs_max_expert_total"):
        if name not in after:
            return None
        grown[name] = after[name] - before.get(name, 0.0)
    held = obs["config"].get("experts_held") or \
        obs["config"].get("n_routed_experts")
    if not held or grown["tpudl_moe_pairs_total"] <= 0:
        return None
    return (grown["tpudl_moe_pairs_max_expert_total"] * held
            / grown["tpudl_moe_pairs_total"])
