"""Share of a chip's idle time in the window whose gap no span of the
program covers (every device plane's gaps, averaged over the planes).
Every gap goes to the innermost ``qrack.*`` span over its middle; what
is left falls to the benchmark's own span or to ``between``.  The whole
table (idle seconds by span) is printed on an earlier line."""

import harness
import program_spans


def read(ctx):
    spans = program_spans.load(ctx)
    if spans is None:
        return None
    idle = spans.idle_by_span()
    harness.say(idle_seconds_by_span={
        k: v / 1e9 for k, v in sorted(idle.items(), key=lambda kv: -kv[1])},
        a_chip_over_planes=spans.planes)
    total = sum(idle.values())
    if not total:
        return 0.0
    mine = sum(v for k, v in idle.items() if k.startswith(program_spans.PROGRAM))
    return 100.0 * (total - mine) / total
