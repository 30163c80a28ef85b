"""Mean per statement of the server writing the answer: `wire/write`,
encode and one `sendall` a packet (the MySQL wire, from inside)."""

import spans


def read(run):
    return spans.mean_ms(run, "wire/write")
