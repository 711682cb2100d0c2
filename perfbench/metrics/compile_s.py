"""Host clock around the first call of every program the window uses: XLA
compilation on a cold compile cache, loading from it on a warm one (the
run's first stderr line says how many entries the cache held)."""


def read(facts):
    return facts["spans"].get("compile_s")
