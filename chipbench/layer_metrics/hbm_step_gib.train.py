"""Device: what the compiler says the step that ran needs on a chip: arguments + outputs +
temporaries - aliased bytes of the compiled step's `memory_analysis()`, which a traced run carries
(GiB). `hbm_peak_gib.train` beside it leaves the temporaries out. None without a trace."""

GIB = 2 ** 30


def read(run):
    m = run.get("memory_analysis") or {}
    if run.get("kind") != "train" or "temp_size_in_bytes" not in m:
        return None
    return (m["argument_size_in_bytes"] + m["output_size_in_bytes"] + m["temp_size_in_bytes"]
            - m["alias_size_in_bytes"]) / GIB
