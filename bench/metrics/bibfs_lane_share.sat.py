"""Share of the BiBFS's lane-waves that did useful work, in %: the waves
in which a real query could still improve, summed over lanes, over
waves x microbatch width. The base counts pad lanes as attempted, so a
part-full microbatch lowers it as much as a lane that finished early."""
from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    mbs = rec.microbatches if rec else ()
    if not mbs or any(m.waves is None for m in mbs):
        return None
    attempted = sum(m.waves for m in mbs) * run.microbatch
    if not attempted:
        return None
    return 100.0 * sum(m.live_lane_waves for m in mbs) / attempted
