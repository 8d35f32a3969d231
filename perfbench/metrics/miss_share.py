"""Share of the window's queries the basis cache missed (cold fits):
``ServiceStats.cache_misses / queries``."""


def read(ctx):
    st = ctx["stats"]
    return 100.0 * st["cache_misses"] / st["queries"] if st.get("queries") else None
