"""Host ms of the submit per answered query: conversion, hashing, checks
and enqueue on the submitting thread (``ServiceStats.submit_s``, the
``drop.submit`` span)."""


def read(ctx):
    s = ctx["stats"].get("submit_s")
    return 1e3 * s / len(ctx["requests"]) if ctx["requests"] and s is not None else None
