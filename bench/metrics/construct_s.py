"""Host seconds of the measured loop's construction, summed over its
`serve.construct.*` spans (generate, load, landmarks, tile, label,
index). Set-up holds two constructions, the warm loop's and this one."""
from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    if rec is None or not rec.construct_s:
        return None
    return float(sum(rec.construct_s.values()))
