"""Mean per statement of `execute/inputs`: snapshot resolve, pins and
the upload of what is not resident."""

import spans


def read(run):
    return spans.mean_ms(run, "execute/inputs")
