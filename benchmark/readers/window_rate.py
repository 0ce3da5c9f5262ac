"""Calls answered correctly inside the window, over the window's seconds.
A request of n calls counts n; one that is answered after the window has
closed counts nothing here."""


def read(ctx):
    w = ctx["window"]
    done = sum(
        n for n, ok, t in zip(w["calls"], w["ok"], w["done"])
        if ok and t <= w["t_end"]
    )
    return done / w["seconds"]
