"""Steps of a sweep as the program schedules it, preamble included.

A transformed sweep is two level-scheduled solves: the T-factor preamble
(`repro.solver.schedule_for_preamble`, the schedule the device pipeline
compiles) and the main schedule (`op.schedule`).  Counting both keeps a
transform that moves rows from the main schedule into the preamble from
lowering the count without fewer steps being run.  On the operator's
host path (`TriangularOperator.solve`) the preamble runs on the host;
its steps are counted all the same, as the steps it takes when it runs
on the device, inside a jitted solver such as PCG.
"""
from __future__ import annotations

__all__ = ["sweep_steps"]


def sweep_steps(op) -> int:
    """Main-schedule steps plus preamble-schedule steps of a
    `TriangularOperator`, the preamble built by the public
    `schedule_for_preamble` with the main schedule's chunk, dependency cap
    and dtype, as the operator builds it for its engines."""
    from repro.solver import schedule_for_preamble
    main = op.schedule
    pre, _, _ = schedule_for_preamble(op.transformed, chunk=main.chunk,
                                      max_deps=main.max_deps,
                                      dtype=main.dtype)
    return int(main.num_steps) + (0 if pre is None else int(pre.num_steps))
