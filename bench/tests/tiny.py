"""Tiny cells for the CPU tests: the real cells' shapes of traffic and
serving, at a model size a test can hold."""
import copy

from bench import spec


def conf(kind: str) -> dict:
    """A two-layer float32 model of the named configuration's family."""
    c = copy.deepcopy(spec._load(spec.BENCH / "configs" / f"{kind}.json"))
    heads, kv = (4, 2) if c["architecture"]["qk_norm"] else (4, 4)
    c["config"].update(num_hidden_layers=2, hidden_size=64,
                       num_attention_heads=heads, num_key_value_heads=kv,
                       head_dim=16, intermediate_size=128, vocab_size=512,
                       torch_dtype="float32")
    return c


def cell(name: str, rate=None, clients=None) -> dict:
    """The named cell at tiny size: a small pool and short sequences."""
    c = copy.deepcopy(spec.cell(name))
    c["name"] = name
    c["conf"] = conf(c["config"])
    c["serving"].update(n_slots=4, capacity=128, n_pages=None)
    for key in ("prompt_tokens", "output_tokens"):
        d = c["mix"][key]
        d.update(median=max(4, d["median"] // 16), min=max(2, d["min"] // 16),
                 max=max(8, d["max"] // 16))
    if "rate_per_s" in c["load"]:
        c["load"]["rate_per_s"] = rate or 4.0
        if "lead_s" in c["load"]:
            c["load"]["lead_s"] = 1.0
    else:
        c["load"]["clients"] = clients or 6
    c["check_sample"] = {"greedy": 3, "sampled": 3}
    c["checks"].update(greedy_gap=1e-3, nucleus_excess=1e-3)
    return c
