"""The readings the correctness limits are set from, on the chip at a
cell's own size: for each seed, one run of the cell (bench/run.run) whose
records are compared three times by the harness's own comparison
(bench/check.py): as served, with the float8 control in the program's
place, and with each sampled token altered (bench/controls.py).

    python3 bench/tools/control.py <cell> <seconds> <seed>...

One JSON line per seed: the program's checks and `correct`, and each
control's checks and `correct` (which should read false).
"""
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2] / "src"),
                str(Path(__file__).resolve().parents[2])]


def main():
    from bench import controls, run, spec

    cell = spec.cell(sys.argv[1])
    seconds = float(sys.argv[2])
    vocab = cell["conf"]["config"]["vocab_size"]
    for seed in map(int, sys.argv[3:]):
        res = run.run(cell["name"], seed, seconds, False, cell=cell,
                      controls={"fp8": controls.fp8,
                                "altered": lambda r, w, a, seed=seed:
                                controls.altered(vocab, seed)})
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "requests": res["attempted"],
                          "checks": res["checks"],
                          "controls": res["controls"]}), flush=True)


if __name__ == "__main__":
    main()
