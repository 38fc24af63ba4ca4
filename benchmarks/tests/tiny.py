"""Shrinks the benchmark's own data files to a size the CPU holds, for the
rehearsal tests: same files, same keys, tiny numbers. Never used by run.py.
A configuration file carries its own small sizes, as its ``tiny`` block: the
widths its reference file reads, the depth per driver, the positions."""

import copy

OUTPUT = {"min": 3, "max": 6, "median": 4}  # a test may lengthen the answers
SAMPLE = 3


def shrink(parts, data):
    data = copy.deepcopy(data)
    kind, name = parts[-2], parts[-1]
    if kind == "configs":
        data.update(data.pop("tiny"))
    elif kind == "workloads":
        data["dtype"] = "float32"  # so that bf16 is the next lower precision in the control tests
        if "step" in data:
            data["step"]["batch"] = 2
        if "engine" in data:
            data["engine"].update(max_slots=4, prompt_bucket=64, max_model_len=128, block_size=8)
            data["rate_rps"] = 12.0
            data["ramp_s"] = 0.5
            data["check"].update(sample=SAMPLE, probe=4)
            # the float32 program reads about 1e-6 here and its int8 paths 1e-2: the cell's
            # own limit is set for bfloat16 at full width
            data["check"]["limits"]["step_logit_rel_rms"] = 1e-3
            data["tail_cap_s"] = 30.0
        data["trace_slice_s"] = 0.5
    elif kind == "traffic":
        if data["kind"] == "batches":
            data["sequence_length"] = 32
        else:
            data["prompt"].update(min=4, max=24, median=10)
            data["output"].update(OUTPUT)
    return data
