"""Harness clock, first spawn to the frontend listing the model."""
SOURCE = "client"


def compute(src):
    return src.facts.get("ready_s")
