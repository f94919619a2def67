"""train_program_hbm_gb (GB), read from program_counter.

The most the step program holds on one chip at any instant, by the
compiler's buffer assignment: ``peak_memory_in_bytes`` of the
``memory_analysis()`` of the executable that ran (``kinds/train_steps.py``
keeps it in traced runs) — its arguments (the resident state), its
temporaries (activations, remat workspaces, gathered weights) and what it
returns beyond what it donates. One program's budget, exact and the same in
every run; ``train_peak_hbm_gb`` beside it is the process's live buffers,
set-up's included, and counts no temporary. A v5e chip offers a program
15.75 GiB = 16.91 GB. The analysis's six fields are written to the run's
detail file (``extra.step_program_memory``); ``argument_size + temp_size``
there is NOT this number: it exceeds it, in the one-chip cell by more than
the chip has (PERF.md section 6, PR 26), so it is not what is held at once.
"""

NAME = "train_program_hbm_gb"
UNIT = "GB"
LAYER = "device"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    memory = record.extra.get("step_program_memory")
    if not memory or record.rehearse:
        return None
    return memory["peak_memory"] / 1e9
