/* Plain scaled JPEG decode for the JPEG store's reference: libjpeg decodes
 * at scale_num/8, where scale_num is the smallest whose output covers
 * (min_w, min_h) as jpeg_calc_output_dimensions computes it, or the
 * scale_num given, into RGB rows.
 *
 * Built by benchmark/stores/jpeg_imagenet.py with
 *   cc -O2 -shared -fPIC jpeg_scaled.c -o <lib> -ljpeg
 */

#include <setjmp.h>
#include <stddef.h>
#include <stdio.h>

#include <jpeglib.h>

struct err {
  struct jpeg_error_mgr mgr;
  jmp_buf jump;
};

static void on_error(j_common_ptr cinfo) { longjmp(((struct err *)cinfo->err)->jump, 1); }

/* dims gets [width, height, scale_num] of the decode; with out NULL
 * nothing is decoded. scale_num 0 chooses the scale. Returns 0, or -1 on a bad image. */
int scaled_decode(const unsigned char *data, unsigned long len, int min_w, int min_h,
                  int scale_num, unsigned char *out, int *dims) {
  struct jpeg_decompress_struct cinfo;
  struct err e;
  cinfo.err = jpeg_std_error(&e.mgr);
  e.mgr.error_exit = on_error;
  if (setjmp(e.jump)) {
    jpeg_destroy_decompress(&cinfo);
    return -1;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, (unsigned char *)data, len);
  jpeg_read_header(&cinfo, TRUE);
  cinfo.out_color_space = JCS_RGB;
  cinfo.scale_denom = 8;
  if (scale_num > 0) {
    cinfo.scale_num = scale_num;
  } else {
    for (cinfo.scale_num = 1; cinfo.scale_num < 8; cinfo.scale_num++) {
      jpeg_calc_output_dimensions(&cinfo);
      if ((int)cinfo.output_width >= min_w && (int)cinfo.output_height >= min_h) break;
    }
  }
  jpeg_calc_output_dimensions(&cinfo);
  dims[0] = (int)cinfo.output_width;
  dims[1] = (int)cinfo.output_height;
  dims[2] = (int)cinfo.scale_num;
  if (out == NULL) {
    jpeg_destroy_decompress(&cinfo);
    return 0;
  }
  jpeg_start_decompress(&cinfo);
  while (cinfo.output_scanline < cinfo.output_height) {
    JSAMPROW row = out + (size_t)cinfo.output_scanline * cinfo.output_width * 3;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return 0;
}
