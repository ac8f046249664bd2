"""Seconds from process start to the window's start: weights, prepare,
warm-up and any compilation (host clock)."""


def read(obs):
    return obs.setup_s
