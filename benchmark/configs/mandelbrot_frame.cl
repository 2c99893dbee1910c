// Copy of the user's kernel as the program ships it (cekirdekler_tpu/workloads.py MANDELBROT_SRC, upstream's mandelbrot demo); the benchmark keeps its own so that the cell does not change when the program's examples do.
__kernel void mandelbrot(__global float* out,
                         float x0, float y0, float dx, float dy,
                         int width, int maxIter) {
    int i = get_global_id(0);
    float cx = x0 + dx * (float)(i % width);
    float cy = y0 + dy * (float)(i / width);
    float zx = 0.0f;
    float zy = 0.0f;
    int it = 0;
    while (zx*zx + zy*zy < 4.0f && it < maxIter) {
        float t = zx*zx - zy*zy + cx;
        zy = 2.0f*zx*zy + cy;
        zx = t;
        it++;
    }
    out[i] = (float)it;
}
