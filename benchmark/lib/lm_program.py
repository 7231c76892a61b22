"""The seam between the benchmark and the program for a token model: the
program's config for a benchmark configuration (`benchmark/lib/program.py`'s
`load_config` is the SPADE configurations': a token model's YAML holds
other sizes in other places). The seed's weights go into the program's
state by `program.py`'s `flatten` and `graft`: reference names are the
program's own paths below `params` and `buffers`, joined by "/".
"""

from __future__ import annotations

import os

from benchmark.lib import harness
from benchmark.lib.program import get_dotted, set_dotted

# benchmark size -> where the program's YAML holds it; every other key of
# `sizes` is `gen.<key>`
ELSEWHERE = {
    "seq_len": "data.seq_len",
    "batch_seqs": "data.train.batch_size",
    "gen_lr": "gen_opt.lr",
    "adam_beta1": "gen_opt.adam_beta1",
    "adam_beta2": "gen_opt.adam_beta2",
}


def load_config(config, shrunk=False):
    """The program's config for this benchmark configuration: its YAML
    with every size checked against the benchmark's own `sizes` (a YAML
    whose widths moved is another configuration). `shrunk` is for the CPU
    rehearsals, which write their small sizes in."""
    from imaginaire_tpu.config import Config

    cfg = Config(os.path.join(harness.ROOT, config["program_yaml"]))
    for dotted, value in config["overrides"].items():
        set_dotted(cfg, dotted, value)
    sizes = dict(config["sizes"], **{"data.vocab_size":
                                     config["sizes"]["vocab_slice"]})
    for key, want in sizes.items():
        dotted = key if "." in key else ELSEWHERE.get(key, "gen." + key)
        if shrunk:
            set_dotted(cfg, dotted, want)
            continue
        have = get_dotted(cfg, dotted)
        have = dict(have) if isinstance(want, dict) else have
        if have != want:
            raise harness.BenchmarkError(
                f"{config['program_yaml']}: {dotted} is {have!r}, the "
                f"benchmark's configuration says {want!r}")
    return cfg
