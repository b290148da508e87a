"""User-supplied row/batch transforms executed on the decode workers.

Parity: /root/reference/petastorm/transform.py:19-64 (``TransformSpec``,
``transform_schema``). The transform runs on the CPU host inside the worker pool,
*before* batches are staged toward the TPU, so its cost overlaps device compute.
"""

from __future__ import annotations

from petastorm_tpu.unischema import Unischema, UnischemaField


class TransformSpec(object):
    """Describes a transform applied to each row dict (row readers) or each
    column batch dict (batch readers) on the worker.

    :param func: callable taking a row dict (or dict of column arrays for batch
        readers) and returning the transformed dict. May be ``None`` if only
        field editing/removal is needed.
    :param edit_fields: list of :class:`UnischemaField` (or
        ``(name, numpy_dtype, shape, nullable)`` tuples) added/replaced by ``func``.
    :param removed_fields: names of fields ``func`` removes.
    :param selected_fields: if not ``None``, an explicit post-transform field-name
        whitelist. (Note: the resulting schema's fields are name-sorted, as in any
        Unischema — selection controls membership, not ordering.)
    :param batched: when True, ``func`` receives a dict of whole columns (one
        ``[N, ...]`` array / object column per field) even on row readers, and
        must return the same — no per-row dict is ever materialized, keeping the
        worker's hot path columnar. Batch readers always pass columns to
        ``func`` regardless of this flag.
    :param image_decode_hints: ``{field_name: (min_h, min_w)}`` — a promise that
        ``func`` will downscale these image fields to at most that size, which
        lets the decode worker use scaled JPEG decode (libjpeg m/8 DCT scaling:
        images arrive at the smallest scale still covering the minimum, so most
        pixels of a large photo are never computed). ``func`` must therefore
        accept images of any size >= the hint (or the original size, if
        smaller) — exactly what a resize-to-target transform does. PNG fields
        are unaffected (no scaled decode exists for the format).
    :param image_resize: ``{field_name: (out_h, out_w)}`` — resize these image
        fields to EXACTLY that size during decode, before ``func`` runs (which
        therefore doesn't need its own resize). The whole column decodes and
        resamples (bilinear below 2x decimation, area at 2x or more) in one
        GIL-released native call straight into a single
        ``[N, out_h, out_w, C]`` allocation (per-image fallback for 16-bit or
        mixed gray/RGB columns, or when the native codec is unavailable),
        removing the per-row Python resize from the host hot loop. Implies the
        scaled-JPEG-decode hint for the field.
        The post-transform schema's shape for the field is updated
        automatically unless ``edit_fields`` overrides it.
    """

    def __init__(self, func=None, edit_fields=None, removed_fields=None, selected_fields=None,
                 batched=False, image_decode_hints=None, image_resize=None):
        self.func = func
        self.edit_fields = [self._as_field(f) for f in (edit_fields or [])]
        self.removed_fields = list(removed_fields or [])
        self.selected_fields = list(selected_fields) if selected_fields is not None else None
        self.batched = batched
        self.image_decode_hints = dict(image_decode_hints or {})
        self.image_resize = {}
        for name, size in (image_resize or {}).items():
            try:
                # a str would pass len()==2 per-character ('24' -> (2, 4))
                ok = (not isinstance(size, (str, bytes))
                      and len(size) == 2 and int(size[0]) >= 1 and int(size[1]) >= 1)
            except (TypeError, ValueError):  # scalar (no len) or non-numeric elements
                ok = False
            if not ok:
                raise ValueError('image_resize[{!r}] must be a positive (out_h, out_w), '
                                 'got {!r}'.format(name, size))
            self.image_resize[name] = (int(size[0]), int(size[1]))
            # resizing to the target IS the downscale promise scaled JPEG
            # decode needs; an explicit hint (if any) wins
            self.image_decode_hints.setdefault(name, self.image_resize[name])

    @staticmethod
    def _as_field(field_or_tuple):
        if isinstance(field_or_tuple, UnischemaField):
            return field_or_tuple
        name, numpy_dtype, shape, nullable = field_or_tuple
        return UnischemaField(name, numpy_dtype, shape, nullable=nullable)


def transform_schema(schema, transform_spec):
    """Derive the post-transform schema (reference transform.py:43-64)."""
    removed = set(transform_spec.removed_fields)
    edited = {f.name: f for f in transform_spec.edit_fields}
    fields = {f.name: f for f in schema if f.name not in removed}
    fields.update(edited)
    for name, (out_h, out_w) in getattr(transform_spec, 'image_resize', {}).items():
        # validate against the ORIGINAL schema (a resized field may legitimately
        # be consumed/removed by func): decode-time resize only happens for
        # codecs that implement it, so anything else must fail loudly here
        # instead of silently yielding unresized data against a lying schema
        src = schema.fields.get(name)
        if src is None:
            raise ValueError('image_resize refers to unknown field {!r}'.format(name))
        if not getattr(src.codec, 'supports_image_resize', False):
            raise ValueError(
                'image_resize[{!r}]: field is stored with {}, which does not support '
                'decode-time resize (only image codecs do); resize it in the transform '
                'func instead'.format(name, type(src.codec).__name__))
        # decode-time resize pins the leading H, W dims; explicit edits win
        f = fields.get(name)
        if f is not None and name not in edited and f.shape is not None and len(f.shape) >= 2:
            fields[name] = UnischemaField(f.name, f.numpy_dtype,
                                          (out_h, out_w) + tuple(f.shape[2:]),
                                          f.codec, f.nullable)
    if transform_spec.selected_fields is not None:
        missing = [n for n in transform_spec.selected_fields if n not in fields]
        if missing:
            raise ValueError('selected_fields not present after transform: {}'.format(missing))
        fields = {n: fields[n] for n in transform_spec.selected_fields}
    return Unischema('{}_transformed'.format(schema.name), list(fields.values()))
