"""Per cent of lane slots doing work: rounds run over lanes x the slowest lane's rounds, per device call."""

from bench import program_spans


def read(record):
    return program_spans.share_pct(record, "sweep", "rounds.lane_rounds",
                                   "rounds.lane_slots")
