"""A ratio of counters: product(num) / product(den) * scale, or one minus
that. A name that is missing reads as nothing (None), a zero denominator
too. params: {"num": [names], "den": [names], "scale": 1.0,
"one_minus": false}"""


def read(obs, params):
    c = obs.counters
    names = list(params["num"]) + list(params.get("den", []))
    if any(n not in c for n in names):
        return None
    num = den = 1.0
    for n in params["num"]:
        num *= c[n]
    for n in params.get("den", []):
        den *= c[n]
    if den == 0:
        return None
    value = num / den
    if params.get("one_minus"):
        value = 1.0 - value
    return value * params.get("scale", 1.0)
