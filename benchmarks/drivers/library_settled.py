"""Traffic kind ``library_settled``: the ``library`` closed loop, with
the window opened on the steady state of an engine whose placement
moves under the first applications.

The pager's remap planner changes the placement table in the first
steps of an evolution and with it the programs a step runs; after that
the table recurs.  This driver is ``library.run`` behind a thin proxy of
the family: ``start`` is the family's own followed by
``settle_applications`` whole applications (their gate calls and their
read), every later application counts from there, and ``final_check``
is told every step the ket took.  The settled applications are part of
``setup_s``: a user pays them.

The engine has to say its placement by a public read,
``q.placement()``; the driver asks before the first gate call, so a
program without it ends there, having built no window program.
"""

import harness

library = harness.load_module("drivers", "library")


class Settled:
    """The family as ``library.run`` sees it: application ``i`` of the
    window is the family's application ``i + settle``."""

    def __init__(self, env):
        self.family, self.env = env.family, env
        self.settle = env.traffic["settle_applications"]

    def _table(self, q, **said):
        table = list(q.placement())
        self.env.say(placement=table, **said)
        return table

    def warmup(self, q, plan, k, spans, checks):
        if k == 0:  # the engine's first use
            self._table(q, before="warmup")
        self.family.warmup(q, plan, k, spans, checks)

    def start(self, q, plan, spans):
        self.family.start(q, plan, spans)
        tables = [self._table(q, before="settle")]
        quiet = harness.Spans()  # the window's spans hold its own steps
        for s in range(self.settle):
            self.family.enqueue(q, plan, s, quiet)
            q.GetAmplitude(self.family.read_index(plan, s))
            tables.append(self._table(q, settled_application=s))
        # a step's plan follows from the table it starts on: where the
        # table after the last settled step is the one two steps before,
        # every later step starts where a settled one did
        self.env.checks.require(
            "placement_is_periodic",
            len(tables) > 2 and tables[-1] == tables[-3],
            f"period {1 if tables[-1] == tables[-2] else 2}")

    def enqueue(self, q, plan, i, spans):
        self.family.enqueue(q, plan, i + self.settle, spans)

    def read_index(self, plan, i):
        return self.family.read_index(plan, i + self.settle)

    def expected(self, plan, i):
        return self.family.expected(plan, i + self.settle)

    def final_check(self, q, plan, last_i, spans, checks):
        self.family.final_check(q, plan, last_i + self.settle, spans, checks)


def run(env):
    env.family = Settled(env)
    return library.run(env)
