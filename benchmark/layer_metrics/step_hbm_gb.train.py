"""Device memory one step needs on a chip, in GB: the compiled step's
``memory_analysis()`` (temporaries + arguments + outputs - aliased) — the
part ``peak_bytes_in_use`` does not see — plus nothing else: the arguments
ARE the live parameters, optimizer state and batch."""


def read(facts):
    mem = facts.get("step_memory")
    if not mem:
        return None
    return (mem["temp_bytes"] + mem["argument_bytes"] + mem["output_bytes"]
            - mem["alias_bytes"]) / 1e9
