"""Deployment configurations of the simulator: ``configs.smr`` holds the
paper's regions, RTT table, batch sizes and budgets (``SMRConfig``)."""
