"""``exposed_collective_share`` for the silos' cell: the share of the
traced window in which a collective runs on a chip while no compute op
runs there, mean over the chips, chiefly the aggregation's psum of the
2.14 GB global model a round."""

from bench.metrics.exposed_collective_share import read  # noqa: F401
