"""Median time to attestation over every window request, at the client:
from sending the query to receiving the attestation's end."""
import statistics


def read(rec):
    return statistics.median(q["done"] - q["sent"] for q in rec["queries"])
