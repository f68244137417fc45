"""Device: what the compiled step says a running step adds to its own
arguments on a chip, in GiB: temporaries plus outputs less the outputs that
alias the donated state (gauges ``step.hbm.temp_bytes`` + ``.output_bytes``
- ``.alias_bytes``, from ``memory_analysis()`` once a signature; arguments,
code and the seconds the account took on standard error). None where the
program read no allocator or booked no step account."""

from benchmark import hbm_account


def read(record):
    parts = [hbm_account.gib(f"step.hbm.{name}")
             for name in ("temp_bytes", "output_bytes", "alias_bytes")]
    if None in parts:
        return None
    hbm_account.say("the step's own account", "step.hbm.argument_bytes",
                    "step.hbm.code_bytes", "step.hbm.account_s")
    return parts[0] + parts[1] - parts[2]
