"""What a thread's stages leave unnamed: ``scale`` times one minus the sum
of the ``share`` of every stage in ``names``, over the one extent
``stage_stat.py`` reads (its module docstring has the rule: the tail left
out, inside the measured window, no older than a full reservoir).

The runtime thread is in exactly one of ``runtime.idle``, ``.stack``,
``.dispatch``, ``.materialize`` and ``.handoff`` at a time, and all but
``.stack`` start at the clock reading the one before ended at, so what
this reads is the thread's time outside all five: the few lines of its
loop before a ``.stack``, the waits that are kept out of the reservoirs
(the one that ends in shutdown), and the spans that reach over the extent's
ends.

Every share is taken by ``stage_stat.reduce`` itself, loaded from the file
beside this one: the extent, the floor and the program's absence are its
business, and where it has nothing to read for any of the names (a program
without that stage, a cell that never loaded the module) neither has this.
"""

import os

import harness

_stage_stat = harness.load_path(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "stage_stat.py")
)


def reduce(obs: dict, names: list, scale: float = 1.0) -> float | None:
    shares = [_stage_stat.reduce(obs, name, "share") for name in names]
    if None in shares:
        return None
    return scale * (1.0 - sum(shares))
