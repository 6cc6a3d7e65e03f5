"""Warm-up calls of the step that handed a program to the compiler (a
persistent-cache hit counts: jax retraced).  Two per process today: the
second call sees parameters whose sharding the first call changed."""


def reduce(trace, run):
    return sum(1 for call in run["warmup"] if call["compiled"])
