"""L4 trainer step: the root mean square of the residual stream on its
way into the final norm, median over the first worker's rounds in the
window.  In a block with scale multipliers (Granite's: 12 on the
looked-up rows, 0.22 on every branch before it joins the stream) it is
what those two set: at the seed the rows enter at ``12 x 0.02 = 0.24``
and ten layers of branches add little, and training moves it with every
output projection.  A reading that runs away upward is a stream that no
branch can steer any more (each branch reads a normed stream and adds
``r`` times an O(1) output); a reading of 0 is a table that carries
nothing.  The benchmark's entry has to name one direction: ``lower``,
away from the runaway.  The program reduces it on the device, an
auxiliary output of the step fetched only while obs records, noted on
the ``round`` span as ``lm_stream_rms`` (one entry; gauge
``mpit_lm_stream_rms``: ``optim/sync.py`` ``note_stats``,
``models/transformer.py`` ``GraniteDecoder``, ``STREAM_RMS``).  Nothing
to read from a program or a block that records none."""

from chipbench.layers import gdn_decay_mean

ARG = "lm_stream_rms"


def read(run):
    return gdn_decay_mean.layers_mean_median(run, ARG)
