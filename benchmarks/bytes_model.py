"""The bytes a window's statements must read from device memory: for
each statement, the columns its template scans x the table's rows x the
width the configuration says a value is stored at. A lower bound on
traffic (intermediates, sorts and exchanges are not counted), which is
what a roofline share wants: it cannot pass 100% unless this counts too
much or the busy time leaves work out."""


def statement_bytes(config: dict, template) -> float:
    width = float(config["stored_bytes_per_value"])
    total = 0.0
    for table, columns in template.scans.items():
        total += config["tables"][table]["rows"] * len(columns) * width
    return total


def window_bytes(config: dict, mix: dict, statements: list) -> float:
    per = {n: statement_bytes(config, t) for n, t in mix["templates"].items()}
    return sum(per[st.template] for st in statements)
