"""Find an open-loop cell's knee, the highest arrival rate its serving
stack sustains, by one sweep on the chip.

    python3 bench/tools/sweep.py <cell> <seconds> <rate>...

One set-up, then a window at each rate in turn (traffic from the cell's
mix, seed 1).  Each prints one JSON line: requests, finished, time to
first token at the median and the 90th percentile over the first and
the second half of the window's requests, the 90th percentile gap
between tokens, and output tokens/s.  Where the second half's time to
first token runs away from the first half's, the queue grows: the rate
is past the knee.
"""
import asyncio
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]


def main():
    from bench import run, spec

    cell = spec.cell(sys.argv[1])
    seconds = float(sys.argv[2])
    rates = [float(r) for r in sys.argv[3:]]
    run.devices(cell["chips"], True)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    parts, cfg, _, stack = run.build(cell, 1)
    batcher = stack.target
    for rate in rates:
        c = dict(cell, load={"rate_per_s": rate})
        reqs = parts.loop.generate(c["mix"], c["load"], 1, seconds,
                                   cfg.vocab_size)
        marks = {}
        win = asyncio.run(run._window(stack, parts.loop, c, reqs, seconds,
                                      None, marks, grace=20.0))
        for req in list(batcher.queue) + [r for r in batcher.slot_req
                                          if r is not None]:
            batcher.cancel(req.rid)
        recs = sorted(win.records, key=lambda r: r.due)
        half = len(recs) // 2
        nr = run.nearest_rank
        print(json.dumps({
            "rate": rate, "requests": len(recs),
            "finished": sum(r.completion is not None for r in recs),
            "ttft_p50_ms": nr([r.ttft for r in recs], 50) * 1e3,
            "ttft_p90_first_ms": nr([r.ttft for r in recs[:half]], 90) * 1e3,
            "ttft_p90_second_ms": nr([r.ttft for r in recs[half:]], 90) * 1e3,
            "tpot_p90_ms": nr([r.tpot for r in recs], 90) * 1e3,
            "output_tok_s": sum(r.in_window for r in recs) / seconds,
            "late_max_ms": max(win.lateness()) * 1e3,
            "counters": run.delta(marks["c_open"], marks["c_end"])}),
            flush=True)


if __name__ == "__main__":
    main()
