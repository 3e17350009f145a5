"""Landing + verify: spans the chip holder landed by the fused native path
over all spans it landed in the window (``df_span_land_total{path}``). A
count of which path ran; it times nothing."""


def read(obs):
    total = sum(obs.span_lands.values())
    if total <= 0:
        return None
    return obs.span_lands.get("native", 0.0) / total
