"""What set-up was made of, from the program's compile ledger
(``dalle_pytorch_tpu/utils/profiling.py:COMPILE_LEDGER``: one record for each
of ``jax.monitoring``'s compile events, on ``time.monotonic()``, the clock
``process_start`` and ``setup_s`` are on). The ledger's ``summary`` over
``[process_start, process_start + setup_s]`` leaves out the window's records
and the reference's own compiles, which come later, and counts a jit traced
inside another's trace once. ``value`` chooses the number:

- ``step_trace_s``, ``step_lower_s``, ``step_load_s``: seconds the step's
  ``program`` was traced in Python (every kernel body inside it included),
  converted to MLIR, and requested from the backend (the persistent cache's
  retrieval in a warm run, the compile in a cold one);
- ``other_programs_s``: the same three, summed, of every other program
  requested in set-up (weights from the seed, the optimizer's state, the
  comparison's reducers);
- ``fresh_compiles``: requests the persistent cache did not serve;
- ``unaccounted_share``: what is left of ``setup_s`` after the four numbers of
  seconds above, in percent: imports, the chip's start, drawing weights, the
  first steps' execution.

A program without the ledger, and a set-up in which ``program`` was never
requested, give None."""

PARTS = ("trace", "lower", "backend")


def _ledger():
    try:
        from dalle_pytorch_tpu.utils.profiling import COMPILE_LEDGER
    except ImportError:
        return None
    return COMPILE_LEDGER if COMPILE_LEDGER.installed_at is not None else None


def read(ctx, value, program="train_step"):
    ledger = _ledger()
    setup_s = ctx.facts.get("setup_s")
    if ledger is None or not setup_s:
        return None
    found = ledger.summary(ctx.process_start, ctx.process_start + setup_s)
    step = found["programs"].get(program)
    if step is None or found["dropped"]:
        return None
    if value == "fresh_compiles":
        return found["cache_misses"]
    own = {"step_trace_s": step["trace"], "step_lower_s": step["lower"],
           "step_load_s": step["backend"]}
    if value in own:
        return own[value]
    compiling = sum(found["seconds"][part] for part in PARTS)
    if value == "other_programs_s":
        return compiling - sum(own.values())
    if value == "unaccounted_share":
        return 100.0 * (setup_s - compiling) / setup_s
    raise ValueError(f"no value {value!r} in the set-up ledger")
