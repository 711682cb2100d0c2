"""Host clock around compile() (and compile_decode()): the strategy search."""


def read(facts):
    return facts["spans"].get("search_s")
